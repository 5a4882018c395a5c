"""model step, serving: (token, expert) assignments to experts HELD on this
chip, per live decoded token and expert layer: d ``moe_assignments_held`` / (d
``live_slot_steps`` x expert layers), as
``moe_held_assignments_per_token.mimo`` reads it, with THIS configuration's
sizes.  With 8 of 256 experts a token and 16 held it reads 8 x 16 / 256 = 0.5
under the group limit as without it (the limit clumps a token's assignments
into 4 groups, it does not change their number): about half the tokens keep
the held experts' group (``moe_group_hit_share.deepseek``) and those have
about 1.0.  A program without the counters reads nothing."""

from benchmarks.lib import needs_deepseek_v3 as needs


def read(report):
  d = needs.counters(report)
  if d is None:
    return None
  return d["moe_assignments_held"] / (
      d["live_slot_steps"] * needs.sizes()["expert_layers"])
