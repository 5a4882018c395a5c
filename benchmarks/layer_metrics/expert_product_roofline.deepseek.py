"""kernels: the grouped expert product's share of its roofline in the
``deepseek-v3-serve-backlog`` cell: the least time the chip could take to stream the
matrices of the experts the traced calls TOUCHED (bytes over the HBM peak,
``benchmarks/lib/peaks``: a step's product is bound by bytes) over the device
time of the kernel's events, ``trace_summary.kernels["%expert_product"]`` (the
name of the innermost jit, ``ops.expert_product``), as
``decode_attention_roofline.mimo`` reads ``%decode_attention``.

``needs_expert_product.roofline_percent`` counts, a call, the mean number of
held experts a layer of a decode step touched (d ``moe_experts_touched`` / (d
``steps`` x expert layers)) times one matrix's bf16 bytes, with THIS
configuration's sizes (``needs_deepseek_v3``).  A prefill chunk's calls carry the
same name and touch every held expert: they are counted at a step's share, so
they can only lower the number and it stays under 100.  The counters are the
measured WINDOW's and the trace the few seconds AFTER it (PERF.md section 7
(3)).  A program without the counters or a trace without the kernel (the parent
of PR 41) reads nothing."""

from benchmarks.lib import needs_deepseek_v3 as needs
from benchmarks.lib import needs_expert_product


def read(report):
  return needs_expert_product.roofline_percent(report, needs)
