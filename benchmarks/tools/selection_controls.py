#!/usr/bin/env python3
"""Builder's reading, never part of a check's run: do the
``keye-vl2-serve-backlog`` cell's limits FAIL the reference computed WITHOUT a
piece of the selection's or the router's mathematics, put in the program's
place?

    chiprun -- python3 benchmarks/tools/selection_controls.py --seed N

``benchmarks/tools/sink_control.py``'s reading (the cell run as
``benchmarks/run.py`` runs it, the reference pass reading one more gap a
control at every checked position) with the controls of the ``keye_vl2``
family: ``no_select`` (every candidate attended: the indexer left out),
``select_half`` (1024 chosen, not 2048), ``no_renorm`` (the router's weights
not renormalised) and ``fp8``, all on ONE run's served requests.  The cell's
runner (``serve_engine_vs_control``) compares the served tokens' mean gap with
the fp8 control's OF THE SAME RUN, so the fp8 reading is handed to the report
as that control and the cell's own checks are printed for the sound run and
for each control in the program's place; and ONE more reading a control: the
mean over the checked tokens BEYOND position ``topk`` alone (below it nothing
is dropped and the first two controls are the model itself).  A file of its
own because the PR that brought it may not edit ``sink_control.py``, which
keeps no positions and hands the report no control.  Writes
``chiprun_out/selection_controls-<cell>-<seed>.json``.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)

CELL = "keye-vl2-serve-backlog"
CONTROLS = "no_select,select_half,no_renorm,fp8"


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--workload", default=CELL)
  ap.add_argument("--seed", type=int, default=0)
  ap.add_argument("--seconds", type=float, default=50.0)
  ap.add_argument("--controls", default=CONTROLS)
  ap.add_argument("--rehearse", action="store_true")
  args = ap.parse_args(argv)
  args.trace, args.control = 0, False
  import numpy as np
  from benchmarks import run as bench_run
  from benchmarks.lib import loader
  from benchmarks.tools import sink_control
  controls = [c for c in args.controls.split(",") if c]
  if "fp8" not in controls:
    raise SystemExit("the cell's check reads the fp8 control: keep it")
  spec = bench_run.build_spec(
      loader.load_json(os.path.join(ROOT, "BENCHMARK.json")), args)
  base = loader.load_module("runners", "serve_engine")     # whose child it is
  runner = loader.load_module("runners", spec["traffic"]["runner"])
  found, far = {}, []

  def reference_gaps(family, config, seed, sample, max_seq, _):
    gaps, _ = sink_control.reference_gaps(family, config, seed, sample,
                                          max_seq, controls, found)
    topk = family.sizes(config)["topk"]
    # a request's gaps are those of positions plen - 1 .. n - 2
    far.extend(np.arange(len(p) - 1, len(p) - 1 + len(g)) >= topk
               for (p, _), g in zip(sample, gaps))
    found["sound"] = gaps
    return gaps, found["fp8"]        # the report's control is the fp8 one

  base._reference_gaps = reference_gaps
  report_path = os.path.join(spec["run_dir"], "serve.json")
  base.child_main(spec, report_path)
  rep = loader.load_json(report_path)
  limits = spec["traffic"]["limits"]
  beyond = np.concatenate(far) if far else np.zeros((0,), bool)

  def verdict(tag):
    g = np.concatenate(found[tag])
    r = dict(rep, served_gap_max=float(g.max()),
             served_gap_mean=float(g.mean()),
             served_gap_p99=float(np.percentile(g, 99)))
    checks = runner.checks_from(r, limits)
    for c in checks:
      print("[selection_controls] %-11s check %-28s value %-22r limit %s "
            "%-8r %s" % (tag, c["name"], c["value"], c["rule"], c["limit"],
                         "ok" if c["ok"] else "FAILED"), flush=True)
    far_g, far_ctl = g[beyond], np.concatenate(found["fp8"])[beyond]
    row = dict(tokens=int(len(far_g)),
               gap_mean=float(far_g.mean()) if len(far_g) else None,
               over_control=float(far_g.mean() / far_ctl.mean())
               if len(far_g) and far_ctl.mean() > 0 else None)
    print("[selection_controls] %-11s beyond position topk: %d tokens, mean "
          "gap %r, over the fp8 control's there %r" % (
              tag, row["tokens"], row["gap_mean"], row["over_control"]),
          flush=True)
    return dict(correct=all(c["ok"] for c in checks),
                gap_max=r["served_gap_max"], gap_p99=r["served_gap_p99"],
                gap_mean=r["served_gap_mean"],
                over_control=r["served_gap_mean"] / rep["control_gap_mean"]
                if rep["control_gap_mean"] else None,
                failed=[c["name"] for c in checks if not c["ok"]],
                beyond_topk=row)

  result = dict(
      cell=spec["cell"], seed=spec["seed"], slots=spec["traffic"]["slots"],
      tokens_in_window=rep["tokens_in_window"], window_s=rep["window_s"],
      checked_tokens=rep["checked_tokens"], limits=limits,
      sound=verdict("sound"))
  for c in controls:
    result[c] = verdict(c)
  out_dir = os.path.join(ROOT, "chiprun_out")
  os.makedirs(out_dir, exist_ok=True)
  with open(os.path.join(out_dir, "selection_controls-%s-%d.json"
                         % (spec["cell"], spec["seed"])), "w") as f:
    json.dump(result, f, indent=1)
  print(json.dumps(result))
  return 0


if __name__ == "__main__":
  sys.exit(main())
