"""SLO compliance report: replay declared objectives over recorded runs.

The live SLO plane (``obs.slo`` driven by the ``AnomalyDetector``) burns
alerts in real time; this tool answers the after-the-fact question "did
the run MEET its objectives" from the artifacts a run leaves behind:

- the per-process obs JSONL logs (``TOS_OBS_DIR``): final metric
  snapshots carry each engine's cumulative quantile SKETCHES
  (``serve.ttft_ms`` / ``serve.e2e_ms`` — ``obs.quantiles``) and the
  availability counters (``serve.submitted/rejected/poisoned``,
  ``fleet.shed``); this tool merges the sketches cluster-wide exactly
  like the live plane and evaluates the same ``obs.slo`` objectives
  into a compliance table, plus every recorded ``slo_burn`` alert.

Objectives come from the same ``TOS_SLO_*`` knobs the live plane reads
(``obs.slo.objectives_from_env``) — report-time env declares what to
grade, or ``--ttft-ms/--e2e-ms/--availability/--quantile`` override.

``--smoke`` is the end-to-end plumbing proof (tier-1-covered, ``make
slo-smoke``): a REAL 2-process LocalEngine cluster serves prompts
through per-executor ``ServingEngine``s with the obs plane + a declared
TTFT objective on, polls the rendezvous HEALTH verb OUT-OF-PROCESS-style
mid-run and asserts the SLO status rides the wire, then merges the logs
and asserts (a) a LINKED request trace (>= 2 spans sharing one
``trace_id``, queue/prefill through stream) and (b) a compliant
objective table — the canary phase's read path, proven end to end.

Usage:  python tools/slo_report.py OBS_DIR [--json-out F]
        python tools/slo_report.py --smoke [--keep DIR]
"""

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: the smoke's declared TTFT bound (ms): generous — the smoke proves
#: plumbing, not latency; a tiny CPU model must grade compliant
_SMOKE_TTFT_MS = 60000.0


# --- smoke main fn (top level: it crosses the engine pickle boundary) --------


def _smoke_serve_main(args, ctx):
  import jax
  import numpy as np
  from tensorflowonspark_tpu.models import transformer as tfm
  from tensorflowonspark_tpu.serving.engine import ServingEngine

  # as small as the engine goes, and ONE prompt length (= one prefill
  # bucket shape): both executors jit concurrently on a small CI box,
  # so every avoided compile pays twice — this smoke proves trace/SLO
  # PLUMBING, the serving suites own engine behavior
  cfg = tfm.TransformerConfig(vocab_size=32, num_layers=1, num_heads=2,
                              d_model=16, d_ff=32, max_seq_len=16,
                              remat=False, dtype=jax.numpy.float32)
  state = tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=8)
  eng = ServingEngine(state.params, cfg, num_slots=2, eos_id=3,
                      horizon=2, buckets=(4,),
                      poll_interval=0.01).start()
  feed = ctx.get_data_feed(train_mode=False)
  try:
    while not feed.should_stop():
      batch = feed.next_batch(4)
      if not batch:
        continue
      prompts = [np.asarray(r, np.int32) for r in batch]
      outs = eng.generate(prompts, max_new_tokens=6, timeout=120,
                          detailed=True)
      # one result per row: the generated length (the driver checks
      # conservation; parity is pinned elsewhere — this run proves the
      # TRACE + SLO plumbing around the engine)
      feed.batch_results([int(len(o["tokens"]) - len(p))
                          for o, p in zip(outs, prompts)])
  finally:
    eng.stop()


# --- compliance over recorded logs -------------------------------------------


def build_compliance(procs, objectives):
  """Evaluate ``objectives`` (obs.slo) against the merged procs' final
  metric snapshots — the offline twin of the detector's live pass:
  sketches merge cluster-wide, availability counters sum."""
  metrics_by_proc = {}
  for i, proc in enumerate(procs):
    m = proc.get("metrics") or {}
    if m:
      metrics_by_proc[i] = m
  rows = []
  for obj in objectives:
    total, bad, observed = obj.totals(metrics_by_proc)
    frac = (bad / total) if total else None
    row = {"objective": obj.name, "kind": obj.kind,
           "events": total, "bad": bad, "bad_frac": frac,
           "budget": obj.budget, "observed": observed,
           # no events = nothing to grade: vacuously compliant, but
           # surfaced as events=0 so a silent no-traffic run can't
           # masquerade as a healthy one
           "compliant": frac is None or frac <= obj.budget}
    if obj.kind == "latency":
      row["threshold_ms"] = obj.threshold_ms
      row["quantile"] = obj.quantile
    else:
      row["target"] = obj.target
    rows.append(row)
  return rows


def collect_slo_alerts(procs):
  """Every recorded ``slo_burn`` alert (the crash-safe per-alert JSONL
  appends), time-ordered."""
  out = []
  for proc in procs:
    for a in proc.get("alerts") or []:
      if a.get("alert") == "slo_burn":
        out.append(a)
  out.sort(key=lambda a: a.get("t", 0.0))
  return out


def print_compliance(rows, alerts):
  w = sys.stderr.write
  if not rows:
    w("no SLO objectives declared (set TOS_SLO_* or pass --ttft-ms/"
      "--e2e-ms/--availability)\n")
  else:
    w("%-16s %-12s %10s %10s %9s %9s  verdict\n"
      % ("objective", "kind", "events", "bad_frac", "budget", "observed"))
    for r in rows:
      if r["kind"] == "latency":
        obs_txt = ("%.1fms" % r["observed"]) \
            if r["observed"] is not None else "-"
      else:
        obs_txt = ("%.5f" % r["observed"]) \
            if r["observed"] is not None else "-"
      w("%-16s %-12s %10d %10s %9.4f %9s  %s\n"
        % (r["objective"], r["kind"], int(r["events"]),
           "%.4f" % r["bad_frac"] if r["bad_frac"] is not None else "-",
           r["budget"], obs_txt,
           "COMPLIANT" if r["compliant"] else "VIOLATED"))
  if alerts:
    w("recorded slo_burn alerts: %d\n" % len(alerts))
    for a in alerts[:8]:
      ev = a.get("evidence") or {}
      w("  t=%.2f %s burn %.1f/%.1f\n"
        % (a.get("t", 0.0), ev.get("objective", "?"),
           ev.get("burn_fast") or 0.0, ev.get("burn_slow") or 0.0))


def objectives_from_args(args):
  from tensorflowonspark_tpu.obs import slo as slo_mod
  if args.ttft_ms is None and args.e2e_ms is None \
      and args.availability is None:
    return slo_mod.objectives_from_env()
  q = args.quantile
  out = []
  if args.availability:
    out.append(slo_mod.Objective("availability", "availability",
                                 target=args.availability))
  if args.ttft_ms:
    out.append(slo_mod.Objective("ttft_p%g" % (100 * q), "latency",
                                 metric="serve.ttft_ms",
                                 threshold_ms=args.ttft_ms, quantile=q))
  if args.e2e_ms:
    out.append(slo_mod.Objective("e2e_p%g" % (100 * q), "latency",
                                 metric="serve.e2e_ms",
                                 threshold_ms=args.e2e_ms, quantile=q))
  return out


def run_report(args):
  from tensorflowonspark_tpu.obs import export

  procs = export.merge_jsonl(export.find_logs(args.obs_dir))
  rows = build_compliance(procs, objectives_from_args(args))
  alerts = collect_slo_alerts(procs)
  print_compliance(rows, alerts)
  result = {"metric": "slo_report", "obs_dir": args.obs_dir,
            "logs": len(procs), "objectives": rows,
            "slo_burn_alerts": len(alerts),
            "compliant": all(r["compliant"] for r in rows)}
  if args.json_out:
    with open(args.json_out, "w") as f:
      json.dump(result, f, indent=2)
  print(json.dumps(result))
  return 0 if result["compliant"] else 3


# --- the smoke run -----------------------------------------------------------


def _linked_traces(procs):
  """``{trace_id: [span names]}`` for every request trace with >= 2
  spans across the merged logs."""
  by_trace = {}
  for proc in procs:
    for rec in proc.get("spans") or []:
      t = rec.get("trace")
      if t:
        by_trace.setdefault(str(t), []).append(rec.get("name", "?"))
  return {t: names for t, names in by_trace.items() if len(names) >= 2}


def run_smoke(keep_dir=None):
  import threading
  import time
  import random

  from tensorflowonspark_tpu.obs import slo as slo_mod

  obs_dir = keep_dir or tempfile.mkdtemp(prefix="tos_slo_smoke_")
  os.environ["TOS_OBS"] = "1"
  os.environ["TOS_OBS_DIR"] = obs_dir
  os.environ.setdefault("TOS_OBS_INTERVAL", "0.25")
  os.environ.setdefault("TOS_OBS_DETECT_INTERVAL", "0.25")
  # a declared latency objective (generous: plumbing, not latency) so
  # the HEALTH wire carries a latency verdict next to availability
  os.environ.setdefault(slo_mod.ENV_SLO_TTFT_MS, str(_SMOKE_TTFT_MS))

  from tensorflowonspark_tpu import cluster as tos_cluster
  from tensorflowonspark_tpu.cluster import InputMode
  from tensorflowonspark_tpu.engine import LocalEngine
  from tensorflowonspark_tpu.obs import export
  from tools.obs_top import poll_health

  rng = random.Random(0)
  # fixed length 4 = the one declared prefill bucket
  parts = [[[rng.randrange(5, 30) for _ in range(4)]
            for _ in range(3)] for _ in range(4)]
  total_rows = sum(len(p) for p in parts)

  engine = LocalEngine(num_executors=2)
  results = []
  feeder_err = []
  slo_wire = None
  try:
    c = tos_cluster.run(engine, _smoke_serve_main,
                        input_mode=InputMode.ENGINE,
                        reservation_timeout=60, heartbeat_interval=0.5)
    addr = tuple(c.server_addr)

    def _feed():
      try:
        results.extend(c.inference(parts, feed_timeout=300))
      except Exception as e:  # noqa: BLE001 - surfaced after the polls
        feeder_err.append(e)

    t = threading.Thread(target=_feed, daemon=True)
    t.start()
    # the out-of-process read: SLO status must ride the HEALTH verb
    client = None
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
      reply, client = poll_health(addr, client=client)
      if reply.get("slo") and (reply["slo"].get("objectives") or []):
        slo_wire = reply["slo"]
        break
      time.sleep(0.3)
    if client is not None:
      client.close()
    t.join(timeout=300)
    c.shutdown(timeout=600)
    if feeder_err:
      raise feeder_err[0]
  finally:
    engine.stop()

  procs = export.merge_jsonl(export.find_logs(obs_dir))
  linked = _linked_traces(procs)
  # a full waterfall: queue wait → prefill → slot-attributed decode on
  # ONE trace id (``stream()`` consumers add a serve.stream leg; this
  # smoke reads via generate(), whose delivery is the result() wait)
  full = {t: names for t, names in linked.items()
          if {"serve.queue", "serve.prefill",
              "serve.decode.slot"} <= set(names)}
  objectives = slo_mod.objectives_from_env()
  rows = build_compliance(procs, objectives)
  alerts = collect_slo_alerts(procs)
  print_compliance(rows, alerts)

  wire_names = sorted(o.get("name", "?")
                      for o in (slo_wire or {}).get("objectives") or [])
  ttft_row = next((r for r in rows if r["objective"].startswith("ttft")),
                  None)
  ok = (len(results) == total_rows
        and slo_wire is not None
        and "availability" in wire_names
        and any(n.startswith("ttft") for n in wire_names)
        and bool(full)
        and ttft_row is not None and ttft_row["events"] >= total_rows
        and all(r["compliant"] for r in rows)
        and not alerts)    # a clean run must not burn
  result = {"metric": "slo_report_smoke", "ok": ok,
            "rows_served": len(results),
            "slo_on_wire": wire_names,
            "linked_traces": len(linked),
            "full_waterfalls": len(full),
            # one real trace id for obs_report --request to chain on
            "sample_trace": sorted(full)[0] if full else None,
            "objectives": rows, "slo_burn_alerts": len(alerts),
            "obs_dir": obs_dir}
  print(json.dumps(result))
  return 0 if ok else 2


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument("obs_dir", nargs="?", default=None,
                  help="directory of obs-*.jsonl logs (TOS_OBS_DIR)")
  ap.add_argument("--ttft-ms", type=float, default=None,
                  help="override: p-quantile TTFT bound in ms")
  ap.add_argument("--e2e-ms", type=float, default=None,
                  help="override: p-quantile e2e latency bound in ms")
  ap.add_argument("--availability", type=float, default=None,
                  help="override: availability target in (0, 1)")
  ap.add_argument("--quantile", type=float, default=0.99,
                  help="the p for --ttft-ms/--e2e-ms (default 0.99)")
  ap.add_argument("--json-out", default=None,
                  help="also write the report JSON here")
  ap.add_argument("--smoke", action="store_true",
                  help="drive a 2-process LocalEngine serve run and "
                       "assert linked traces + SLO status over HEALTH")
  ap.add_argument("--keep", default=None,
                  help="--smoke: keep the obs logs in this directory")
  args = ap.parse_args()
  if args.smoke:
    sys.exit(run_smoke(keep_dir=args.keep))
  if not args.obs_dir:
    ap.error("obs_dir is required (or use --smoke)")
  sys.exit(run_report(args))


if __name__ == "__main__":
  main()
