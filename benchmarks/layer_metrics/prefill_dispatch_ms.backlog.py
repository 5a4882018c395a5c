"""serving scheduler and slots: the loop thread's milliseconds a prefill
chunk's dispatch, d ``t_prefill_s`` / d ``prefill_chunks`` (the program's
counters, there since PR 24 and 27): the host's slice-and-pad and the
runtime's call; where chunks run ahead of the device it holds their calls'
waits for memory too."""

from benchmarks.lib import empty


def read(report):
  return empty.ms_per(report, "t_prefill_s", "prefill_chunks")
