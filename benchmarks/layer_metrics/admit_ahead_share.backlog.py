"""serving scheduler and slots: percent of the window's admissions that took
a lane the running decode dispatch was certain to free (its request's budget
ended inside the horizon) before that dispatch had been read, d
``admits_ahead`` / d ``prefills`` (the program's counters): without them the
lane would stand empty for one dispatch.  A program without the counter (the
parent of PR 39) reads nothing."""


def read(report):
  d = report.get("stats_delta") or {}
  if not d.get("prefills") or "admits_ahead" not in d:
    return None
  return 100.0 * d["admits_ahead"] / d["prefills"]
