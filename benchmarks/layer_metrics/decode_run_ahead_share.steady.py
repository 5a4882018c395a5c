"""serving scheduler and slots: percent of the window's ``step_many``
dispatches made while the step before was still unread, d
``decode_dispatches_ahead`` / d ``decode_dispatches`` (the program's
counters), in the open loop: under 100 by the first step of every run of
steps, which has no step to run ahead of (an engine that was empty).  A
program without the counter (the parent of PR 42) reads nothing."""


def read(report):
  d = report.get("stats_delta") or {}
  if not d.get("decode_dispatches") or "decode_dispatches_ahead" not in d:
    return None
  return 100.0 * d["decode_dispatches_ahead"] / d["decode_dispatches"]
