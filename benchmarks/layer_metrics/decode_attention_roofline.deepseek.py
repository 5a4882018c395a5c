"""kernels: the decode-attention kernel's share of its roofline in the
``deepseek-v3-serve-backlog`` cell: the least time the chip could take for
what the traced calls needed of their latent leaf, the LARGER of bytes over
the HBM peak and FLOPs over the bf16 peak (``benchmarks/lib/peaks``: 128 heads
over one 1280-byte row are 217.6 FLOP a byte, beside the chip's ridge of 240,
so either roof may be the one), over the device time of the kernel's events,
``trace_summary.kernels["%decode_attention"]`` (the name of the innermost jit,
``ops.decode_attention``), as ``decode_attention_roofline.mimo`` reads it.

Every call of the kernel in this cell reads ONE latent leaf over all slots
(five a step, all alike); what it NEEDS is each live row once, for scores and
values (the kernel as built is handed the leaf as K and as V and brings a
block twice: the share says what that costs).  The trace
does not say how many rows a call read, the program's counters do: the mean
live rows a call are ``live_context_tokens`` / ``steps``;
``needs_deepseek_v3.decode_attention_bytes`` / ``decode_attention_flops``
turn rows into bytes and FLOPs.

It counts LIVE rows only, each row's 576 numbers against 128 heads and its 512
values under 128 probabilities: whole blocks read past a cursor, the lanes of
padding, the queries and the output are not what the attention needs of the
cache, so the share stays under 100.  The counters are the measured WINDOW's
and the trace the few seconds AFTER it (PERF.md section 7 (3)): the same load,
a little later.  A program without the counters, one whose latent reads did
not take the kernel, or a trace without the kernel reads nothing."""

from benchmarks.lib import needs_deepseek_v3 as needs
from benchmarks.lib import peaks

KERNEL = "%decode_attention"


def read(report):
  d = needs.counters(report)
  k = ((report.get("trace_summary") or {}).get("kernels") or {}).get(KERNEL)
  if d is None or not k or not k["seconds"] > 0 \
      or not d["decode_attn_reads_ragged"]:
    return None
  rows = d["live_context_tokens"] / d["steps"]
  least, _ = peaks.roofline_seconds(
      k["calls"] * needs.decode_attention_flops(rows),
      k["calls"] * needs.decode_attention_bytes(rows),
      report["device"]["kind"])
  return 100.0 * least / k["seconds"]
