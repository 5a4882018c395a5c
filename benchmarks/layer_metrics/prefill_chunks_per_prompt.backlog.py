"""serving scheduler and slots: chunk dispatches per admitted prompt in the
closed loop, d ``prefill_chunks`` / d ``prefills`` (the program's counters):
how many 36-layer programs the prefill plan makes of the mix's prompts, each
a dispatch the chip waits for.  ``prefill_chunks_per_prompt`` reads the same
in the steady cell."""

from benchmarks.lib import phases


def read(report):
  return phases.prefill_chunks_per_prompt(report)
