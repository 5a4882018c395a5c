"""serving scheduler: 95th percentile of ``Request.queue_wait`` (submit to
admission into a slot)."""

from benchmarks.lib import stats


def read(report):
  waits = [(r["started_at"] - r["submitted_at"]) * 1e3
           for r in report.get("requests", []) if r.get("started_at")]
  return stats.percentile(waits, 95) if waits else None
