"""serving scheduler and slots: percent of the window in which the loop
thread KNEW its device drained: d(sum of the ten ``empty_*_s``) / window (the
program's counters, ``obs.spans.DeviceQueue``).  From the return of a
blocking read of the newest program's output to the return of the next
dispatch call nothing can be running, so this is a lower bound of
``device_idle_share.backlog``, over the whole window and not 3 s of trace;
the remainder is idle the host cannot vouch for (launch, gaps inside a
program, the copy back)."""

from benchmarks.lib import empty


def read(report):
  return empty.empty_share(report)
