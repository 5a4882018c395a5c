"""serving scheduler and slots: live slot-steps over slot-steps offered in
the window (the engine's own counters, as a delta)."""


def read(report):
  d = report.get("stats_delta")
  if not d or not d.get("steps"):
    return None
  return 100.0 * d["live_slot_steps"] / (d["steps"] * report["slots"])
