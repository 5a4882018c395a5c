"""load generator: how late the open loop sent against its schedule."""

from benchmarks.lib import stats


def read(report):
  if report.get("loop") != "open":
    return None
  late = [r["late_s"] * 1e3 for r in report["requests"]]
  return stats.percentile(late, 95) if late else None
