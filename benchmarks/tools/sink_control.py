#!/usr/bin/env python3
"""Builder's reading, never part of a check's run: do a serving cell's limits
FAIL the reference computed WITHOUT its attention sinks, put in the program's
place?

    chiprun -- python3 benchmarks/tools/sink_control.py --workload <cell> --seed N

A sink is one scalar a query head in a window layer's softmax denominator: a
small term, and a program that dropped it would still serve fluent tokens.
``benchmarks/run.py --control`` puts the reference in fp8 in the program's
place (``runners/serve_engine.py``'s ``control_fn``); this tool does the same
with ``reference_logits(..., precision=<control>)`` for each of
``--controls`` (default ``no_sink,fp8``: both controls on ONE run's served
requests, so that one call gives a sound reading and both controls).  It runs
the cell as ``benchmarks/run.py`` does (the runner's own child, in this
process), with the reference pass replaced by one that also reads, at every
checked position, the gap (under the float32 reference) of the token each
control puts first; then it prints the cell's checks for the sound run and for
each control in its place, with the cell's own limits.  A file of its own
because the PR that brought it may not edit ``runners/serve_engine.py``.
Writes ``chiprun_out/sink_control-<cell>-<seed>.json``; for families whose
``reference_logits`` takes the control's name as ``precision``
(``mimo_v2_flash``).
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)


def reference_gaps(family, config, seed, sample, max_seq, controls, out):
  """``runners.serve_engine._reference_gaps`` with one more reading a
  control; fills ``out`` (``{control: [gaps of a request, ...]}``)."""
  import numpy as np
  import jax
  import jax.numpy as jnp
  w = family.make_weights(seed, config, "bfloat16")   # the served numbers

  @jax.jit
  def gaps_fn(w, toks):
    z = family.reference_logits(w, toks, config)[0]           # [S, V]
    best = jnp.max(z[:-1], axis=-1)
    served = jnp.take_along_axis(z[:-1], toks[0, 1:, None], axis=-1)[:, 0]
    return best - served, z

  def control_fn(precision):
    def fn(w, toks, z):
      low = family.reference_logits(w, toks, config, precision)[0]
      first = jnp.argmax(low[:-1], axis=-1)
      picked = jnp.take_along_axis(z[:-1], first[:, None], axis=-1)[:, 0]
      return jnp.max(z[:-1], axis=-1) - picked
    return jax.jit(fn)

  fns = {c: control_fn(c) for c in controls}
  gaps = []
  for prompt, tokens in sample:
    n, plen = len(prompt) + len(tokens), len(prompt)
    buf = np.zeros((1, max_seq), np.int32)      # causal: the tail is inert
    buf[0, :plen], buf[0, plen:n] = prompt, tokens
    g, z = gaps_fn(w, jnp.asarray(buf))
    gaps.append(np.asarray(g)[plen - 1:n - 1])
    for c, fn in fns.items():
      out.setdefault(c, []).append(
          np.asarray(fn(w, jnp.asarray(buf), z))[plen - 1:n - 1])
    del z
  return gaps, []


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--workload", required=True)
  ap.add_argument("--seed", type=int, default=0)
  ap.add_argument("--seconds", type=float, default=50.0)
  ap.add_argument("--controls", default="no_sink,fp8")
  ap.add_argument("--override", action="append", default=[],
                  metavar="KEY=JSON")
  ap.add_argument("--rehearse", action="store_true")
  args = ap.parse_args(argv)
  args.trace, args.control = 0, False
  import numpy as np
  from benchmarks import run as bench_run
  from benchmarks.lib import loader
  spec = bench_run.build_spec(
      loader.load_json(os.path.join(ROOT, "BENCHMARK.json")), args)
  for item in args.override:
    key, _, value = item.partition("=")
    spec["traffic"][key] = json.loads(value)
  base = loader.load_module("runners", "serve_engine")     # whose child it is
  runner = loader.load_module("runners", spec["traffic"]["runner"])
  controls, found = [c for c in args.controls.split(",") if c], {}
  base._reference_gaps = lambda family, config, seed, sample, max_seq, _: \
      reference_gaps(family, config, seed, sample, max_seq, controls, found)
  report_path = os.path.join(spec["run_dir"], "serve.json")
  base.child_main(spec, report_path)
  rep = loader.load_json(report_path)
  limits = spec["traffic"]["limits"]

  def verdict(tag, r):
    checks = runner.checks_from(r, limits)
    for c in checks:
      print("[sink_control] %-8s check %-28s value %-22r limit %s %-12r %s"
            % (tag, c["name"], c["value"], c["rule"], c["limit"],
               "ok" if c["ok"] else "FAILED"), flush=True)
    return dict(correct=all(c["ok"] for c in checks),
                gap_max=r["served_gap_max"], gap_p99=r["served_gap_p99"],
                gap_mean=r["served_gap_mean"],
                failed=[c["name"] for c in checks if not c["ok"]])

  result = dict(
      cell=spec["cell"], seed=spec["seed"], slots=spec["traffic"]["slots"],
      tokens_in_window=rep["tokens_in_window"], window_s=rep["window_s"],
      checked_tokens=rep["checked_tokens"], limits=limits,
      sound=verdict("sound", rep))
  for c in controls:
    g = np.concatenate(found[c])
    result[c] = verdict(c, dict(
        rep, served_gap_max=float(g.max()), served_gap_mean=float(g.mean()),
        served_gap_p99=float(np.percentile(g, 99))))
  out_dir = os.path.join(ROOT, "chiprun_out")
  os.makedirs(out_dir, exist_ok=True)
  with open(os.path.join(out_dir, "sink_control-%s-%d.json"
                         % (spec["cell"], spec["seed"])), "w") as f:
    json.dump(result, f, indent=1)
  print(json.dumps(result))
  return 0


if __name__ == "__main__":
  sys.exit(main())
