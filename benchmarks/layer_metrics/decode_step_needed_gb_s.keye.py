"""model step, serving: the bytes a decode step NEEDS as the program reads its
cache (``benchmarks/lib/needs_keye_vl2.py``: the weights every token passes,
the experts touched, the live context's rows in the six layers at 2304 B a
token: index key, keys and values, read WHOLE under the keep rows, a row a
live lane written in each; from the program's counters and the
configuration's sizes) per second of ``decode_step_inner_ms``, in GB/s.

NOT a share of a roofline, for the reason ``decode_step_needed_gb_s.kimi``
gives: the denominator is the loop thread's own clock round the dispatch and
the wait for the token matrix, not the device time of the ``step_many``
program (PERF.md section 7 (6)).  What a read of the chosen rows alone would
still bring is ``sparse_rows_kept_share.keye``'s to say.  A program without
the counters reads nothing."""

from benchmarks.lib import needs_keye_vl2 as needs
from benchmarks.lib import phases


def read(report):
  d = needs.counters(report)
  step_ms = phases.decode_step_inner_ms(report)
  if d is None or not step_ms:
    return None
  nbytes = needs.decode_step_bytes(
      d["live_slot_steps"] / d["steps"], d["moe_experts_touched"] / d["steps"],
      d["live_context_tokens"] / d["steps"])
  return nbytes / 1e9 / (step_ms / 1e3)
