"""What the grouped expert product's kernel (``ops.expert_product``) NEEDS to
read, for ``expert_product_roofline.*``: the matrices of the experts that got
at least one row, once each, and nothing else.

A call is one product of one expert layer (gate, up or down: three a layer
application), bound by bytes in a decode step (its rows are 1-3 a group) and
near them in a prefill chunk (30-64 rows a group).  The trace does not say how
many groups a call touched, the program's counters do for the decode steps: d
``moe_experts_touched`` / (d ``steps`` x expert layers) is the mean number of
held experts with at least one live token in a layer of a step.  One matrix is
a third of an expert (``hidden_size x moe_intermediate_size`` bf16 numbers:
gate, up and down have the same count).  The sizes are the cell's own
``needs_*.py``'s.

It counts the TOUCHED matrices of a decode call only: the rows read, the
result written and, in a prefill chunk (whose calls carry the same name and
touch every held expert, not a step's share), the other matrices are not
counted, so chunk calls in the trace can only lower the share and it stays
under 100.
"""

from benchmarks.lib import peaks

KERNEL = "%expert_product"
BF16 = 2


def least_seconds(report, needs):
  """``(least seconds, traced seconds)`` of the kernel's traced calls by the
  cell's ``needs`` module (its ``counters`` and ``sizes``), or ``None`` where
  the program has no such counters, the window saw no step, or the trace has
  no such kernel (the parent of PR 41)."""
  d = needs.counters(report)
  k = ((report.get("trace_summary") or {}).get("kernels") or {}).get(KERNEL)
  if d is None or not k or not k["seconds"] > 0:
    return None
  z = needs.sizes()
  touched = d["moe_experts_touched"] / (d["steps"] * z["expert_layers"])
  a_call = touched * z["expert_params"] / 3.0 * BF16
  return (k["calls"] * a_call / peaks.chip_peaks(
      report["device"]["kind"])["hbm_bytes_per_s"], k["seconds"])


def roofline_percent(report, needs):
  """100 x least / traced seconds, or ``None`` (:func:`least_seconds`)."""
  both = least_seconds(report, needs)
  return None if both is None else 100.0 * both[0] / both[1]
