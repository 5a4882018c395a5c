"""model step, serving: the bytes a decode step NEEDS
(``benchmarks/lib/needs_kimi_linear.py``: KDA state of the live lanes, the
weights every token passes, the experts touched, the latent cache of the
live context; from the program's counters and the configuration's sizes)
per second of ``decode_step_inner_ms``, in GB/s.

NOT a share of a roofline: the denominator is the loop thread's own clock
round the dispatch and the wait for the token matrix (the program's
``t_decode_dispatch_s`` + ``t_decode_fetch_s``), not the device time of the
``step_many`` program, which the harness's trace reduction does not give by
program (PERF.md section 7).  It moves with the machine as the step's time
does; beside the HBM peak of ``benchmarks/lib/peaks.py`` it says how far a
step is from what its bytes alone would take.  A program without the
counters reads nothing."""

from benchmarks.lib import needs_kimi_linear as needs
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
