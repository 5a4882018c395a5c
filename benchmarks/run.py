#!/usr/bin/env python3
"""The benchmark's entry point.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data: ``BENCHMARK.json`` names the cell's
configuration and traffic mix; ``benchmarks/configs/<config>.json`` names its
``family`` (``benchmarks/families/<family>.py``);
``benchmarks/traffic/<traffic>.json`` names its ``runner``
(``benchmarks/runners/<runner>.py``); each per-layer metric is
``benchmarks/layer_metrics/<metric>.py`` with one ``read(report)`` function.
This file names none of them.

This process never initialises JAX: the runner starts the one process that
holds the chip.  A run that finds no TPU (or fewer chips than the cell asks
for) exits non-zero and prints no result line.  ``--rehearse`` drives the
same code on the CPU at the toy sizes the data files carry under
``"rehearse"``; it prints no result line and no device metric.
"""

import argparse
import json
import math
import os
import shutil
import sys
import time

T_START = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)


from benchmarks.lib.loader import load_json, load_module  # noqa: E402


def log(msg: str) -> None:
  print("[bench] %s" % msg, flush=True)


def _named(entries, name: str, what: str) -> dict:
  for e in entries:
    if e["name"] == name:
      return e
  raise SystemExit("no %s named %r in BENCHMARK.json" % (what, name))


def _applies(metric: dict, cell: str) -> bool:
  return "workloads" not in metric or cell in metric["workloads"]


def _rehearsed(d: dict, rehearse: bool) -> dict:
  """A data file's dict with its ``"rehearse"`` overrides applied."""
  out = {k: v for k, v in d.items() if k != "rehearse"}
  if rehearse:
    out.update(d.get("rehearse", {}))
  return out


def build_spec(bench: dict, args) -> dict:
  cell = _named(bench["workloads"], args.workload, "workload")
  conf_entry = _named(bench["configs"], cell["config"], "configuration")
  config = load_json(os.path.join(ROOT, conf_entry["file"]))
  traffic = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
  run_dir = os.path.join(ROOT, ".bench_runs", "%s-%d-%d" % (
      cell["name"], args.seed, os.getpid()))
  os.makedirs(run_dir, exist_ok=True)
  return dict(cell=cell["name"], chips=int(cell["chips"]),
              config_name=cell["config"],
              config=_rehearsed(config, args.rehearse),
              traffic=_rehearsed(traffic, args.rehearse),
              seed=int(args.seed), seconds=float(args.seconds),
              trace=bool(args.trace), rehearse=bool(args.rehearse),
              control=bool(args.control), run_dir=run_dir,
              t_start=T_START)


def _finite(x) -> bool:
  return isinstance(x, (int, float)) and math.isfinite(x)


def collect_metrics(bench: dict, spec: dict, report: dict) -> dict:
  """``--trace 0``: the cell's end-to-end metrics from the report;
  ``--trace 1``: its per-layer metrics, each from its own reader."""
  cell, out = spec["cell"], {}
  if not spec["trace"]:
    for m in bench["end_to_end"]:
      if _applies(m, cell):
        out[m["name"]] = dict(value=report["end_to_end"][m["name"]],
                              unit=m["unit"])
    return out
  for m in bench["per_layer"]:
    if not _applies(m, cell):
      continue
    value = load_module("layer_metrics", m["name"]).read(report)
    if value is None:
      continue                      # a reader that found nothing to read
    if not _finite(value):
      raise SystemExit("per-layer metric %s read %r" % (m["name"], value))
    out[m["name"]] = dict(value=value, unit=m["unit"])
  return out


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--workload", required=True)
  ap.add_argument("--seed", type=int, default=0)
  ap.add_argument("--seconds", type=float, default=None)
  ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
  ap.add_argument("--keep-run-dir", action="store_true",
                  help="leave .bench_runs/<run> (node report, trace) behind")
  ap.add_argument("--control", action="store_true",
                  help="builder's reading, never part of a check's run: also "
                       "put the reference in a lower precision (fp8) in the "
                       "program's place and print what the comparison reads")
  ap.add_argument("--override", action="append", default=[],
                  metavar="KEY=JSON",
                  help="builder's sweeps: replace one key of the traffic "
                       "file for this run (never part of a check's run)")
  ap.add_argument("--rehearse", action="store_true",
                  help="CPU rehearsal at toy sizes; prints no result line")
  args = ap.parse_args(argv)
  bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
  if args.seconds is None:
    args.seconds = float(bench["run_seconds"])
  spec = build_spec(bench, args)
  for item in args.override:
    key, _, value = item.partition("=")
    spec["traffic"][key] = json.loads(value)
  runner = load_module("runners", spec["traffic"]["runner"])
  # processes the runner starts (executors, nodes, children) import benchmarks
  os.environ["PYTHONPATH"] = os.pathsep.join(
      [ROOT] + [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
                if p])
  log("cell %s: config %s, runner %s, seed %d, %.0f s, trace %d%s"
      % (spec["cell"], spec["config_name"], spec["traffic"]["runner"],
         spec["seed"], spec["seconds"], args.trace,
         ", REHEARSAL (no device metric is printed)" if args.rehearse else ""))
  try:
    report = runner.run(spec)       # raises on any failure: no result line
  finally:
    if not args.keep_run_dir:
      shutil.rmtree(spec["run_dir"], ignore_errors=True)
  if "jax" in sys.modules and not args.rehearse:
    raise SystemExit("the benchmark's parent touched JAX")

  dev = report["device"]
  tag = "platform=%s device_kind=%r devices=%d" % (
      dev["platform"], dev["kind"], dev["count"])
  for line in report.get("notes", []):
    log("%s | %s" % (tag, line))
  for c in report["checks"]:
    log("%s | check %-28s value %-22r limit %s %-12r %s"
        % (tag, c["name"], c["value"], c["rule"], c["limit"],
           "ok" if c["ok"] else "FAILED"))
  correct = all(c["ok"] for c in report["checks"])
  if args.rehearse:
    log("rehearsal done: correct=%s, checks=%d, attempted=%d, failed=%d"
        % (correct, len(report["checks"]), report["attempted"],
           report["failed"]))
    return 0 if correct else 1
  if dev["platform"] != "tpu" or dev["count"] < spec["chips"]:
    raise SystemExit("needs %d TPU chip(s), ran on %s" % (spec["chips"], tag))

  device = dict(platform=dev["platform"], kind=dev["kind"],
                count=dev["count"],
                memory_peak_bytes=report["memory_peak_bytes"])
  result = dict(correct=correct, attempted=report["attempted"],
                failed=report["failed"],
                metrics=collect_metrics(bench, spec, report), device=device)
  summary = report.get("trace_summary")
  if spec["trace"]:
    if not summary or not summary["busy_s"] > 0:
      raise SystemExit("traced run saw no operation on the device")
    device["busy_s"] = summary["busy_s"]
    device["window_s"] = summary["window_s"]
    result["breakdown"] = dict(device_ops=summary["device_ops"][:10],
                               idle_gaps=summary["idle_gaps"][:10])
  print(json.dumps(result), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())
