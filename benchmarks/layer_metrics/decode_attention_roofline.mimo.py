"""kernels: the decode-attention kernel's share of its roofline in the
``mimo-serve-backlog`` cell: the least time the chip could take to read the
LIVE rows of K and V the traced calls needed (bytes over the HBM peak,
``benchmarks/lib/peaks``: a step's attention is bound by bytes) over the
device time of the kernel's events, ``trace_summary.kernels
["%decode_attention"]`` (the name of the innermost jit,
``ops.decode_attention``), as ``flash_fwd_roofline`` reads ``%_fwd_impl``.

A call reads ONE leaf pair over all slots, and this cell has two kinds: a full
layer's (the lanes' whole contexts at 2560 B a row) and a window layer's ring
(``min(cursor, 128)`` rows a lane at 5120 B a row).  The trace does not say
which leaf a call read, the program's counters do: ``decode_attn_reads_ring``
of ``decode_attn_reads`` are over a ring (5 of 7 here), and the mean live rows
a call are ``live_context_tokens`` / ``steps`` (full) and
``window_context_tokens`` / ``steps`` (ring); ``needs_mimo_v2_flash.
decode_attention_bytes`` turns rows into bytes.

It counts LIVE rows only: whole blocks read past a cursor, the queries and the
output are not what the attention needs of the cache, so the share stays under
100.  The counters are the measured WINDOW's and the trace the few seconds
AFTER it (PERF.md section 7 (3)): the same load, a little later.  A program
without the counters or a trace without the kernel reads nothing."""

from benchmarks.lib import needs_mimo_v2_flash as needs
from benchmarks.lib import peaks

KERNEL = "%decode_attention"


def read(report):
  d = needs.counters(report)
  k = ((report.get("trace_summary") or {}).get("kernels") or {}).get(KERNEL)
  if d is None or not k or not k["seconds"] > 0 or not d["decode_attn_reads"]:
    return None
  ring = d["decode_attn_reads_ring"] / d["decode_attn_reads"]
  a_call = ring * needs.decode_attention_bytes(
      d["window_context_tokens"] / d["steps"], True) \
      + (1.0 - ring) * needs.decode_attention_bytes(
          d["live_context_tokens"] / d["steps"], False)
  least = k["calls"] * a_call / peaks.chip_peaks(
      report["device"]["kind"])["hbm_bytes_per_s"]
  return 100.0 * least / k["seconds"]
