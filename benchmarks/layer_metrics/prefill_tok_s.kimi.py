"""model step, serving: prompt tokens prefilled per second of prefill: over
the requests the window finished, the sum of their prompt lengths over the
sum of their prefill spans (admission to prefill done: the bucketed chunks,
the wait for the last and the insert), from the engine's per-request
ledger."""


def read(report):
  spans = [(r["prompt_len"], r["prefill_done_at"] - r["started_at"])
           for r in report.get("requests", [])
           if r.get("started_at") and r.get("prefill_done_at")]
  seconds = sum(s for _, s in spans)
  if not seconds > 0:
    return None
  return sum(n for n, _ in spans) / seconds
