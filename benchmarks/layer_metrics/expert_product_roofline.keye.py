"""kernels: the grouped expert product's share of its roofline in the
``keye-vl2-serve-backlog`` cell, as ``expert_product_roofline.deepseek`` reads
it (``needs_expert_product.roofline_percent``: the matrices of the experts a
decode call TOUCHED, streamed once, over the HBM peak, against the device time
of ``trace_summary.kernels["%expert_product"]``), with THIS configuration's
sizes (``needs_keye_vl2``: a matrix of 2048 x 768 bf16 numbers, 3.1 MB).  A
prefill chunk's calls carry the same name and touch every held expert: they
are counted at a step's share, so they can only lower the number and it stays
under 100.  A program without the counters or a trace without the kernel reads
nothing."""

from benchmarks.lib import needs_keye_vl2 as needs
from benchmarks.lib import needs_expert_product


def read(report):
  return needs_expert_product.roofline_percent(report, needs)
