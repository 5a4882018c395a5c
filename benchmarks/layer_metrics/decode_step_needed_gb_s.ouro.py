"""model step, serving: the bytes a decode step NEEDS
(``benchmarks/lib/needs_ouro.py``: the layers' weights once a PASS, the head
once, the live context's keys and values read in every pass of every layer,
a row a live lane written in each; from the program's counters and the
configuration's sizes) per second of ``decode_step_inner_ms``, in GB/s.

NOT a share of a roofline, for the reason in
``decode_step_needed_gb_s.kimi.py``: the denominator is the loop thread's
own clock round the dispatch and the wait for the token matrix, not the
device time of the ``step_many`` program (PERF.md section 7 (6)).  Beside
the HBM peak of ``benchmarks/lib/peaks.py`` it says how far a step is from
what its bytes alone would take.  A program without the counters reads
nothing."""

from benchmarks.lib import needs_ouro as needs
from benchmarks.lib import phases


def read(report):
  d = needs.counters(report)
  step_ms = phases.decode_step_inner_ms(report)
  if d is None or not step_ms:
    return None
  nbytes = needs.decode_step_bytes(d["live_slot_steps"] / d["steps"],
                                   d["live_context_tokens"] / d["steps"])
  return nbytes / 1e9 / (step_ms / 1e3)
