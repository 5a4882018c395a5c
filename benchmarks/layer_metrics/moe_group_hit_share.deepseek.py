"""model step, serving: percent of the live decoded tokens (a token an expert
layer) whose router kept the GROUP the held experts lie in: d
``moe_group_hits`` / (d ``live_slot_steps`` x expert layers).  The router
keeps 4 of 8 groups a token and this chip's 16 experts are half of group 0, so
it reads about 50 with weights from a seed; a token that did not keep the
group can have no assignment here, whatever its scores: what the published
node limit buys a deployment (a token's 8 experts lie on at most 4 of 8
nodes).  A SANITY reading of the router and not a lever: with a seeded
router it reads about 50 by construction, and no optimisation of the program
should move it (``better`` and ``moves`` are what the schema wants of every
entry); a reading far from 50 says the group limit or the counter broke.  A
program without the counter (no group limit) reads nothing."""

from benchmarks.lib import needs_deepseek_v3 as needs


def read(report):
  d = needs.counters(report)
  if d is None:
    return None
  return 100.0 * d["moe_group_hits"] / (
      d["live_slot_steps"] * needs.sizes()["expert_layers"])
