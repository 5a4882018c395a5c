"""model step, serving: percent of the held experts that got at least one
live token, over the window's decode steps and layers: d
``moe_experts_touched`` / (d ``steps`` x layers x experts held), as
``moe_experts_touched_share.mimo`` reads it, with THIS configuration's sizes
(6 layers, 16 held).  It is the share of the expert weights a step has to
read: with 16 live lanes of one assignment each an expert is untouched with
probability 0.9375^16 = 0.36, so it reads about 64.  A program without the
counters reads nothing."""

from benchmarks.lib import needs_keye_vl2 as needs


def read(report):
  d = needs.counters(report)
  if d is None:
    return None
  z = needs.sizes()
  return 100.0 * d["moe_experts_touched"] / (
      d["steps"] * z["expert_layers"] * z["held"])
