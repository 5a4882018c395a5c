"""device: 1 - (union of device-operation intervals) / traced span, from
the profiler's trace (benchmarks/lib/trace)."""

from benchmarks.lib import trace


def read(report):
  return trace.idle_share_percent(report)
