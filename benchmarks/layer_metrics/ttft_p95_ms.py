"""serving scheduler and slots: 95th percentile of time to first token, from
the moment the request was DUE (a failed, rejected or unfinished request
counts as infinite and is then refused as a reading).  Not an end-to-end
metric: at 0.8 of the knee it swings by 10% from run to run on one schedule
(PERF.md, PR 23), more than any admissible bound."""

import math

from benchmarks.lib import stats


def read(report):
  if report.get("loop") != "open" or not report.get("requests"):
    return None
  value = stats.percentile([stats.ttft_ms(r) for r in report["requests"]], 95)
  return None if value == math.inf else value
