"""model step, serving: the program's own decode step, from its counters:
d(``t_decode_dispatch_s`` + ``t_decode_fetch_s``) / d ``steps``: the call
returning plus the wait for the token matrix, with the harvest, reap and
admission bookkeeping that ``decode_step_ms`` folds in left out."""

from benchmarks.lib import phases


def read(report):
  return phases.decode_step_inner_ms(report)
