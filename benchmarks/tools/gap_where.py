#!/usr/bin/env python3
"""Builder's reading, never part of a check's run: WHERE a serving cell's
widest served-logit gaps lie, and whether a moved router near-tie explains
each of them.

    chiprun -- python3 benchmarks/tools/gap_where.py --workload <cell> --seed N

Runs the cell as ``benchmarks/run.py`` does (``runners/serve_engine.py``'s
own child, in this process), with the reference pass replaced by one that
keeps every served token's gap BY POSITION and, in the reference's own
router, each position's margin between the last place chosen and the first
left out (the k-th and (k+1)-th of ``s + bias``) in every expert layer. For
each gap over ``--floor`` it then runs the reference AGAIN with ONE near-tie
of that position broken the other way (the selection score of the first
expert left out raised over the last chosen, nothing else touched) and reads
the same token's gap again; it tries the position's ties that involve an
expert held here, smallest margin first, up to ``--tries`` (a tie between two
experts held elsewhere moves nothing; a tie is the k-th place against the
(k+1)-th, or, for three scores within rounding, their neighbours the
(k-1)-th and the (k+2)-th). A gap that a moved near-tie made
falls to rounding's size; a gap that a fault made (rows of the window
missing, a wrong position) stays. Also prints the device's memory counters
at each stage. Writes ``chiprun_out/gap_where-<cell>-<seed>.json``; for
families with a ``route(x, w, z)`` of their own (``trinity``).
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)


def _mem(tag):
  import jax
  s = jax.local_devices()[0].memory_stats() or {}
  print("[gap_where] memory %-22s in_use %.3f GB peak %.3f reserved %.3f "
        "largest_free %.3f limit %.3f" % ((tag,) + tuple(
            s.get(k, 0) / 1e9 for k in (
                "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
                "largest_free_block_bytes", "bytes_limit"))), flush=True)


def reference_gaps(family, config, seed, sample, max_seq, control, floor,
                   tries, out):
  """``runners.serve_engine._reference_gaps`` with positions, margins and
  the second reading; fills ``out`` (a list of dicts, one a request)."""
  import numpy as np
  import jax
  import jax.numpy as jnp
  z = family.sizes(config)
  k, layers = z["top_k"], z["layers"] - z["dense_layers"]
  w = family.make_weights(seed, config, "bfloat16")
  _mem("reference weights")

  def forward(w, toks, nudge):
    seen = []

    def route(x, lw, zz):
      s = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x,
                                    lw["router"].astype(jnp.float32)))
      top, experts = jax.lax.top_k(
          s + lw["router_bias"] + nudge[lw["exp_at"]], k + 2)
      # places k-1 .. k+2 (for k = 4: the 3rd to the 6th)
      seen.append((top[0, :, k - 2:], experts[0, :, k - 2:]))
      picked = jnp.take_along_axis(s, experts[..., :k], axis=-1)
      return experts[..., :k], picked / (
          jnp.sum(picked, -1, keepdims=True) + family.ROUTE_EPS) * zz["scale"]

    plain, family.route = family.route, route
    try:
      logits = family.reference_logits(w, toks, config)[0]
    finally:
      family.route = plain
    best = jnp.max(logits[:-1], axis=-1)
    served = jnp.take_along_axis(logits[:-1], toks[0, 1:, None], axis=-1)[:, 0]
    return (best - served, jnp.stack([t for t, _ in seen]),
            jnp.stack([e for _, e in seen]))

  forward = jax.jit(forward)
  first, last = z["first"], z["first"] + z["held"]
  # a near-tie is (the place that leaves, the place that enters) among the
  # four kept: the last chosen against the first left out, and their
  # neighbours for a tie of three
  ties = ((1, 2), (0, 2), (1, 3), (0, 3))
  gaps = []
  for prompt, tokens in sample:
    n, plen = len(prompt) + len(tokens), len(prompt)
    buf = np.zeros((1, max_seq), np.int32)
    buf[0, :plen], buf[0, plen:n] = prompt, tokens
    nudge = np.zeros((layers, max_seq, z["routed"]), np.float32)
    g, top, place = (np.asarray(a) for a in forward(w, buf, nudge))
    at = np.arange(plen - 1, n - 1)          # the position whose logits chose
    g, top, place = g[at], top[:, at], place[:, at]
    here = (place >= first) & (place < last)           # [layers, n, 4]
    # every (layer, tie) of a position, smallest margin first; a tie between
    # two experts held elsewhere moves nothing here
    margin = np.stack([top[..., a] - top[..., b] for a, b in ties], axis=-1)
    moves = np.stack([here[..., a] | here[..., b] for a, b in ties], axis=-1)
    flat = np.where(moves, margin, np.inf).transpose(1, 0, 2).reshape(
        len(at), -1)                                   # [n, layers * ties]
    order = np.argsort(flat, axis=1)
    wide = [int(i) for i in np.flatnonzero(g > floor)]
    read = {i: [] for i in wide}   # (layer, leaves, enters, margin, the gap)
    for rank in range(tries):
      todo = [i for i in wide if np.isfinite(flat[i, order[i, rank]])
              and not any(x[-1] < floor / 4 for x in read[i])]
      if not todo:
        break
      nudge[:] = 0.0
      for i in todo:
        layer, t = divmod(int(order[i, rank]), len(ties))
        a, b = ties[t]
        nudge[layer, at[i], place[layer, i, a]] = -1.0
        nudge[layer, at[i], place[layer, i, b]] = 1.0
      again = np.asarray(forward(w, buf, nudge)[0])[at]
      for i in todo:
        layer, t = divmod(int(order[i, rank]), len(ties))
        read[i].append((layer, k - 1 + ties[t][0], k - 1 + ties[t][1],
                        float(flat[i, order[i, rank]]), float(again[i])))
    out.append(dict(
        prompt_len=plen, out_tokens=len(tokens), gap_max=float(g.max()),
        gap_p99=float(np.percentile(g, 99)), gap_mean=float(g.mean()),
        margin_p50=[float(x) for x in np.median(margin[..., 0], axis=1)],
        margin_p01=[float(x) for x in
                    np.percentile(margin[..., 0], 1, axis=1)],
        gaps=[round(float(x), 4) for x in g],
        wide=[dict(position=int(at[i]), decoded=int(at[i] - plen + 1),
                   gap=float(g[i]), moved=read[i],
                   gap_tie_moved=min([x[-1] for x in read[i]] + [float(g[i])]),
                   margins=[float(x) for x in margin[:, i, 0]],
                   held=[bool(x) for x in moves[:, i, 0]],
                   around=[round(float(x), 3)
                           for x in g[max(i - 3, 0):i + 4]])
              for i in wide]))
    gaps.append(g)
  _mem("reference done")
  return gaps, []


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--workload", required=True)
  ap.add_argument("--seed", type=int, default=0)
  ap.add_argument("--seconds", type=float, default=50.0)
  ap.add_argument("--floor", type=float, default=0.25,
                  help="gaps over this are read a second time")
  ap.add_argument("--tries", type=int, default=4,
                  help="near-ties tried a position, smallest margin first")
  ap.add_argument("--override", action="append", default=[],
                  metavar="KEY=JSON")
  ap.add_argument("--rehearse", action="store_true")
  args = ap.parse_args(argv)
  args.trace, args.control = 0, False
  from benchmarks import run as bench_run
  from benchmarks.lib import loader
  spec = bench_run.build_spec(
      loader.load_json(os.path.join(ROOT, "BENCHMARK.json")), args)
  for item in args.override:
    key, _, value = item.partition("=")
    spec["traffic"][key] = json.loads(value)
  runner = loader.load_module("runners", "serve_engine")   # whose child it is
  family = loader.load_module("families", spec["config"]["family"])
  from tensorflowonspark_tpu import serving
  found = []
  runner._reference_gaps = lambda *a: reference_gaps(
      *a, args.floor, args.tries, found)
  params, start = family.program_params, serving.ServingEngine.start

  def program_params(*a, **kw):
    out = params(*a, **kw)
    import jax
    jax.block_until_ready(out)
    _mem("program weights")
    return out

  def started(eng):
    out = start(eng)
    _mem("engine started")
    return out

  family.program_params, serving.ServingEngine.start = program_params, started
  report_path = os.path.join(spec["run_dir"], "serve.json")
  runner.child_main(spec, report_path)
  rep = loader.load_json(report_path)
  result = dict(
      cell=spec["cell"], seed=spec["seed"], slots=spec["traffic"]["slots"],
      tokens_in_window=rep["tokens_in_window"], window_s=rep["window_s"],
      memory_stats_at_window_close=rep["memory_stats"],
      checked_tokens=rep["checked_tokens"],
      served_gap_max=rep["served_gap_max"],
      served_gap_p99=rep["served_gap_p99"],
      served_gap_mean=rep["served_gap_mean"],
      served_gap_max_ties_moved=max(
          [moved.get(i, g) for r in found for moved in [
              {e["decoded"]: e["gap_tie_moved"] for e in r["wide"]}]
           for i, g in enumerate(r["gaps"])] or [None]),
      requests=found)
  out_dir = os.path.join(ROOT, "chiprun_out")
  os.makedirs(out_dir, exist_ok=True)
  with open(os.path.join(out_dir, "gap_where-%s-%d.json"
                         % (spec["cell"], spec["seed"])), "w") as f:
    json.dump(result, f, indent=1)
  for r in found:
    for e in r["wide"]:
      print("[gap_where] prompt %5d position %5d (decoded %3d) gap %.3f -> "
            "%.3f with a tie moved %s; margins %s held %s; around %s" % (
                r["prompt_len"], e["position"], e["decoded"], e["gap"],
                e["gap_tie_moved"],
                " ".join("L%d/%d>%d/%.1e:%.3f" % x for x in e["moved"]),
                " ".join("%.1e" % m for m in e["margins"]),
                "".join("NY"[x] for x in e["held"]), e["around"]))
  print(json.dumps({k: v for k, v in result.items() if k != "requests"}))
  return 0


if __name__ == "__main__":
  sys.exit(main())
