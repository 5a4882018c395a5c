"""model step, serving: percent of the held experts that got at least one
live token, over the window's decode steps and expert layers: d
``moe_experts_touched`` / (d ``steps`` x expert layers x experts held), as
``moe_experts_touched_share.mimo`` reads it, with THIS configuration's sizes
(4 expert layers, 16 held).  It is the share of the expert weights a step has
to read.  A program without the counters reads nothing."""

from benchmarks.lib import needs_deepseek_v3 as needs


def read(report):
  d = needs.counters(report)
  if d is None:
    return None
  z = needs.sizes()
  return 100.0 * d["moe_experts_touched"] / (
      d["steps"] * z["expert_layers"] * z["held"])
