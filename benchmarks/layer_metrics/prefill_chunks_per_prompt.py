"""serving scheduler and slots: chunk dispatches per admitted prompt,
d ``prefill_chunks`` / d ``prefills`` (the program's counters): how many
36-layer dispatches the bucket plan makes of the mix's prompts."""

from benchmarks.lib import phases


def read(report):
  return phases.prefill_chunks_per_prompt(report)
