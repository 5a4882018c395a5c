"""serving scheduler and slots: percent of the window's time WITH a request
in which the loop thread knew its device drained: d(sum of the ten
``empty_*_s`` - ``empty_idle_s``) / (window - d ``t_idle_s``) (the program's
counters).  ``device_idle_share.steady`` counts the empty engine; this leaves
it out of both sides: the chip kept waiting by the host while a request was
there."""

from benchmarks.lib import empty


def read(report):
  return empty.starved_share(report)
