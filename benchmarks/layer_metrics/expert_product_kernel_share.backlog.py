"""model step, serving: percent of the window's grouped products of held
experts (gate, up and down of each expert layer application, in the prefill
chunks and the fused decode dispatches alike) that took ``ops.expert_product``,
the kernel that reads only the rows that have a group and streams each touched
matrix once, and not ``lax.ragged_dot``, which costs each touched expert about
three times its bytes' time whatever its rows: d ``expert_products_kernel`` /
d ``expert_products`` (the program's counters:
``SlotDecoder`` knows at trace time which lowering each product of a program
took, ``ServingEngine`` adds a program's count a dispatch).  Under 100 some
product fell back: a float32 stack, a width off whole lanes (the rehearsal's
toy widths), a mesh of more than one device.  A program without the counters
(the parent of PR 41) or without such products (no expert layer) reads
nothing."""


def read(report):
  d = report.get("stats_delta") or {}
  if not d.get("expert_products") or "expert_products_kernel" not in d:
    return None
  return 100.0 * d["expert_products_kernel"] / d["expert_products"]
