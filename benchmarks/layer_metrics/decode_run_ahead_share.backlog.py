"""serving scheduler and slots: percent of the window's ``step_many``
dispatches made while the step before was still unread, d
``decode_dispatches_ahead`` / d ``decode_dispatches`` (the program's
counters): such a step's lane state came from that step's outputs on the
device, so the copy back, the harvest and the dispatch call itself passed
while the chip ran.  A program without the counter (the parent of PR 42)
reads nothing."""


def read(report):
  d = report.get("stats_delta") or {}
  if not d.get("decode_dispatches") or "decode_dispatches_ahead" not in d:
    return None
  return 100.0 * d["decode_dispatches_ahead"] / d["decode_dispatches"]
