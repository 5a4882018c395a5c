"""model step, serving: (token, expert) assignments to experts HELD on this
chip, per live decoded token and expert layer: d ``moe_assignments_held`` / (d
``live_slot_steps`` x expert layers), as
``moe_held_assignments_per_token.trinity`` reads it, with THIS configuration's
sizes.  With 8 of 256 experts a token and 16 held it reads 8 x 16 / 256 = 0.5
while the router keeps its published width; a router narrowed to the experts
here would read 8.  A program without the counters reads nothing."""

from benchmarks.lib import needs_mimo_v2_flash as needs


def read(report):
  d = needs.counters(report)
  if d is None:
    return None
  return d["moe_assignments_held"] / (
      d["live_slot_steps"] * needs.sizes()["expert_layers"])
