"""model step, serving: window time not spent in prefills, per decode step.
The engine's ``serve.decode_ms`` histogram needs the obs registry, which a
measured run leaves off, so this is (window - sum of prefill spans of the
requests admitted in it) / decode steps: exact only while the engine is never
idle (a backlog)."""


def read(report):
  d = report.get("stats_delta")
  if not d or not d.get("steps"):
    return None
  w0, w1 = report["w0"], report["w1"]
  prefill = sum(r["prefill_done_at"] - r["started_at"]
                for r in report["requests"]
                if r.get("started_at") and r.get("prefill_done_at")
                and w0 <= r["started_at"] and r["prefill_done_at"] <= w1)
  return 1e3 * (report["window_s"] - prefill) / d["steps"]
