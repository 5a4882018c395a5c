"""model step, serving: (token, expert) assignments to experts HELD on this
chip, per live decoded token and layer: d ``moe_assignments_held`` / (d
``live_slot_steps`` x layers), as ``moe_held_assignments_per_token.mimo``
reads it, with THIS configuration's sizes.  With 8 of 128 softmax experts a
token and 16 held it reads 8 x 16 / 128 = 1.0 with weights from a seed (the
deployment's 8 chips x 16 lanes would bring an expert 8 tokens a step, this
chip's 16 lanes one).  A program without the counters reads nothing."""

from benchmarks.lib import needs_keye_vl2 as needs


def read(report):
  d = needs.counters(report)
  if d is None:
    return None
  return d["moe_assignments_held"] / (
      d["live_slot_steps"] * needs.sizes()["expert_layers"])
