"""serving scheduler and slots: the loop thread's milliseconds a fused decode
dispatch, d ``t_decode_dispatch_s`` / d ``decode_dispatches`` (the program's
counters, there since PR 24): the ``step_many`` call returning, made once
the last step's tokens have been fetched."""

from benchmarks.lib import empty


def read(report):
  return empty.ms_per(report, "t_decode_dispatch_s", "decode_dispatches")
