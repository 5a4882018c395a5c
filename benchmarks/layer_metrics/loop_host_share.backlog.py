"""serving scheduler and slots: percent of the window the loop thread spent
in Python alone, having dispatched nothing and waiting for nothing:
d(``t_reap_s`` + ``t_admit_s`` + ``t_decode_prep_s`` +
``t_decode_harvest_s``) / window (the program's counters)."""

from benchmarks.lib import phases


def read(report):
  return phases.loop_host_share(report)
