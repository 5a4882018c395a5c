"""model step, serving: median of admission to prefill done (the chunked
prefill of one prompt, insert included)."""

from benchmarks.lib import stats


def read(report):
  spans = [(r["prefill_done_at"] - r["started_at"]) * 1e3
           for r in report.get("requests", [])
           if r.get("started_at") and r.get("prefill_done_at")]
  return stats.percentile(spans, 50) if spans else None
