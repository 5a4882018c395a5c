"""kernels: the decode-attention kernel's share of its roofline in the
``keye-vl2-serve-backlog`` cell: the least time the chip could take for what
the traced calls needed of their K and V leaves under the keep rows (bytes
over the HBM peak, ``benchmarks/lib/peaks``: 32 query heads over 4 KV heads of
128 are 16 FLOP a byte, far under the chip's ridge; the larger of the two
roofs is taken all the same), over the device time of the kernel's events,
``trace_summary.kernels["%decode_attention"]`` (the name of the innermost jit,
``ops.decode_attention``), as ``decode_attention_roofline.mimo`` reads it.

Every call of the kernel in this cell reads ONE layer's K and V leaves over
all slots (six a step, all alike) and a keep row a slot.  The trace does not
say how many rows a call read, the program's counters do: the mean live rows a
call are ``live_context_tokens`` / ``steps``;
``needs_keye_vl2.decode_attention_bytes`` / ``decode_attention_flops`` turn
rows into bytes and FLOPs.

It counts LIVE rows only (2048 B of keys and values and one byte of keep
each): whole blocks read past a cursor, the keep rows' four bytes an entry as
built, the queries and the output are not what the attention needs of the
cache, so the share stays under 100.  It does NOT count only the rows KEPT: the
kernel as built reads every live row (``sparse_rows_kept_share.keye`` says
what a read of the chosen rows alone would bring).  The counters are the
measured WINDOW's and the trace the few seconds AFTER it (PERF.md section 7
(3)).  A program without the counters, one whose reads did not take the
kernel, or a trace without the kernel reads nothing."""

from benchmarks.lib import needs_keye_vl2 as needs
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
