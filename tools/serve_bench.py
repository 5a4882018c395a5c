"""Serving micro-bench: KV-cache decode throughput (tokens/sec).

The training side has `bench.py`; this is the serving side of the perf
story — batched greedy decode through the per-layer KV cache
(`models.transformer.greedy_generate_kv`, the path
`make_serving_predict_fn` packages for `TFModel.transform`). Decode is
memory-bound (every step re-reads the whole cache), so the headline
lever is grouped-query attention: the cache and its per-step HBM reads
shrink num_heads/num_kv_heads×. Measures MHA vs GQA at the bench model
shape and prints ONE JSON line.

`--compare` measures the OTHER serving lever — request-level
(continuous) batching: a seeded mixed-length (Zipf-ish) workload is
replayed through (a) the static fixed-batch loop, where a batch of
`--slots` requests decodes to the slowest member's budget and the next
batch waits, and (b) `serving.ServingEngine`, where a finished slot is
refilled immediately. Reports aggregate tokens/sec (useful tokens only
— pads don't count), slot occupancy, and p50/p99 request latency, and
verifies every engine output is BIT-IDENTICAL to the single-request
decode of the same prompt. `--smoke` shrinks the shapes for CI.

`--prefix-workload` measures the decode-speed STACK (paged KV slab,
shared-prefix cache, self-speculative decode) on the workload it exists
for: N distinct system prompts × Zipf fan-out with short tails. Four
persistent engines serve the same seeded workload — the PR 10 contiguous
baseline at the HBM budget's slot count, then one engine per added stage
(paged at equal HBM → more concurrent slots, +prefix cache, +speculative
decode) — so every stage's bit-parity and contribution are gated
independently; `slots_at_equal_hbm` carries the capacity comparison.

`--fleet` measures the REPLICA ROUTER (`serving.ServingFleet`,
docs/ROBUSTNESS.md §Fleet): the same seeded Zipf workload through one
engine vs N same-shape replicas behind the fleet's load-aware dispatch,
with a FULL rolling param swap fired mid-run (swap-in engines
pre-warmed, the canary pattern). Reports fleet vs single goodput and
p50/p99 latency and GATES the fleet claims: zero accepted requests shed
through the swap, every output bit-identical to its single-request
decode, zero cross-replica replay mismatches.

`--fleet --cross-host` runs the SAME fleet over executor-resident
`ServingHost` processes behind the rendezvous wire (`serving.host` /
`serving.remote`, docs/ROBUSTNESS.md §Cross-host serving): paired
in-process vs cross-host passes, a v1→v2 rolling swap ACROSS the
process boundary (registry-built models), and a chaos leg where
`TOS_CHAOS_HOST` SIGKILLs one host mid-decode — ejection, bit-identical
failover replay and a post-kill zero-shed swap are all hard gates.

`--chaos` measures the engine's SELF-HEALING cost (docs/ROBUSTNESS.md):
the same workload runs paired — one clean pass, one with deterministic
`TOS_CHAOS_SERVE` faults injected into the decode dispatch — through
ONE engine, and the report carries degraded goodput (chaos vs clean
tokens/s), recovery latency (crash → in-flight work replay-requeued,
off `ServingEngine.restart_log`), and the replay/restart counters. The
acceptance bar rides along: every recovered output must stay
BIT-IDENTICAL to its single-request decode (greedy replay parity).

Usage: python tools/serve_bench.py [--batch 8] [--prompt 128] [--steps 128]
       python tools/serve_bench.py --compare [--smoke] [--json-out f.json]
       python tools/serve_bench.py --chaos [--smoke] [--json-out f.json]
       python tools/serve_bench.py --fleet [--smoke] [--json-out f.json]
       python tools/serve_bench.py --fleet --cross-host [--smoke]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench as _bench  # noqa: E402 - bench model shape, one source


def measure_speculative(batch, prompt_len, steps, k=4):
  """Self-draft speculative decode (draft == target): acceptance is 100%,
  so the rate isolates the MECHANISM's cost — k draft steps + one
  k-token verify per k emitted tokens vs k sequential target steps. With
  a real (cheaper) draft the chip-side speedup scales from here by
  t_draft/t_target; with a self-draft the useful signal is how close the
  verify pass is to one step (batched positions amortize the weight
  read)."""
  import numpy as np
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import transformer as tfm

  cfg = tfm.TransformerConfig(
      vocab_size=_bench.TFM_VOCAB, num_layers=_bench.TFM_LAYERS,
      num_heads=_bench.TFM_HEADS, d_model=_bench.TFM_DMODEL,
      d_ff=_bench.TFM_DFF, max_seq_len=prompt_len + steps + k,
      remat=False)
  state = tfm.create_state(jax.random.PRNGKey(0), cfg,
                           seq_len=prompt_len + steps)
  rng = np.random.RandomState(0)
  prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, prompt_len)),
                       jnp.int32)

  def run():
    return tfm.speculative_generate_kv(state.params, cfg, state.params,
                                       cfg, prompt, steps, draft_k=k)

  jax.block_until_ready(run())
  t0 = time.perf_counter()
  jax.block_until_ready(run())
  return batch * steps / (time.perf_counter() - t0)


def measure(cfg_kwargs, batch, prompt_len, steps):
  import numpy as np
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import transformer as tfm

  cfg = tfm.TransformerConfig(
      vocab_size=_bench.TFM_VOCAB, num_layers=_bench.TFM_LAYERS,
      num_heads=_bench.TFM_HEADS, d_model=_bench.TFM_DMODEL,
      d_ff=_bench.TFM_DFF, max_seq_len=prompt_len + steps, remat=False,
      **cfg_kwargs)
  state = tfm.create_state(jax.random.PRNGKey(0), cfg,
                           seq_len=prompt_len + steps)
  rng = np.random.RandomState(0)
  prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, prompt_len)),
                       jnp.int32)

  def decode(n):
    return tfm.greedy_generate_kv(state.params, cfg, prompt, n)

  # isolate DECODE from prefill: time a full run and a 1-step run and
  # divide the extra tokens by the extra time (the bench.py subtraction
  # trick) — otherwise the prompt's prefill forward pollutes the rate
  for n in (1, steps):
    jax.block_until_ready(decode(n))   # compile + warm both lengths
  t0 = time.perf_counter()
  jax.block_until_ready(decode(steps))
  dt_full = time.perf_counter() - t0
  t0 = time.perf_counter()
  jax.block_until_ready(decode(1))
  dt_one = time.perf_counter() - t0
  if dt_full - dt_one <= 0.2 * dt_full:
    tok_s = batch * steps / dt_full    # noise floor: conservative
  else:
    tok_s = batch * (steps - 1) / (dt_full - dt_one)
  # decode(1) is prefill-ONLY: the prompt apply itself yields token 1 and
  # the scan runs num_steps-1 = 0 iterations — so dt_one IS the prompt
  # cost (the flash-prefill lever's target, transformer._decode_attend)
  return tok_s, dt_one * 1e3


# --- continuous vs static batching (--compare) ------------------------------

#: compare-mode model/workload shapes: (full, smoke). The claim under
#: test is SCHEDULING-level (slot-steps reclaimed from finished rows),
#: so a small model keeps the CPU run honest and fast; chip-scale decode
#: rates ride the existing per-config modes above.
_COMPARE_FULL = dict(layers=2, heads=4, d_model=128, d_ff=256, vocab=512,
                     requests=48, slots=4, plens=(4, 8, 12, 16),
                     budgets=(8, 16, 32, 64, 96), max_seq=112, horizon=8)
_COMPARE_SMOKE = dict(layers=2, heads=2, d_model=32, d_ff=64, vocab=64,
                      requests=8, slots=3, plens=(4, 6, 8),
                      budgets=(4, 8), max_seq=24, horizon=4)


def _lat_stats(lats):
  """p50/p99 request latency through the SHARED production estimator
  (``obs.quantiles.QuantileSketch`` — the same latency object the
  engines record TTFT/e2e into for the SLO plane), so a bench number
  and a production SLO number are the same kind of number. Returns
  ``(stats dict, agreement bool)``: agreement checks the sketch's
  answers against the exact sorted list within the sketch's own
  self-reported rank-error bound (``--smoke`` gates on it)."""
  import bisect
  from tensorflowonspark_tpu.obs import quantiles
  vals = [float(v) for v in lats if v is not None]
  sk = quantiles.QuantileSketch()
  sk.extend(vals)
  stats = {"p50_s": round(sk.quantile(0.5), 3),
           "p99_s": round(sk.quantile(0.99), 3)}
  sv = sorted(vals)
  tol = sk.rank_error + 1   # +1: nearest-rank vs target-rank rounding
  ok = True
  for q in (0.5, 0.99):
    v = sk.quantile(q)
    lo = bisect.bisect_left(sv, v)
    hi = bisect.bisect_right(sv, v)
    target = q * len(sv)
    if not (lo - tol <= target <= hi + tol):
      ok = False
  return stats, ok


def _zipf_pick(rng, options, a=1.3):
  """Zipf-ish draw over ``options`` sorted ascending: small values
  common, large values rare — the mixed-length traffic shape that makes
  fixed-batch decode waste slot-steps."""
  ranks = 1.0 / (1.0 + __import__("numpy").arange(len(options))) ** a
  p = ranks / ranks.sum()
  return options[rng.choice(len(options), p=p)]


def make_workload(shape, seed):
  """Seeded mixed-length request list: (prompt ndarray, budget) pairs."""
  import numpy as np
  rng = np.random.RandomState(seed)
  reqs = []
  for _ in range(shape["requests"]):
    plen = _zipf_pick(rng, sorted(shape["plens"]))
    budget = _zipf_pick(rng, sorted(shape["budgets"]))
    prompt = rng.randint(0, shape["vocab"], (plen,)).astype(np.int32)
    reqs.append((prompt, int(budget)))
  return reqs


def _reference_streams(params, cfg, workload, eos_id):
  """Per-request single-request greedy decode, truncated at the stop —
  the parity oracle AND the definition of 'useful tokens' both modes are
  scored by."""
  import numpy as np
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import transformer as tfm
  streams = []
  for prompt, budget in workload:
    out = np.asarray(tfm.greedy_generate_kv(
        params, cfg, jnp.asarray(prompt)[None], budget,
        eos_id=eos_id, pad_id=0))[0]
    gen = out[len(prompt):]
    stops = np.where(gen == eos_id)[0]
    stop = (int(stops[0]) + 1) if len(stops) else budget
    streams.append(gen[:stop])
  return streams


def _static_groups(workload, slots):
  """Arrival-order batching under the fixed-shape loop's constraint:
  a batch holds EQUAL-length prompts (stacking is the only thing the
  fixed-shape path can do — padding mixed lengths would corrupt
  outputs), flushing at ``slots`` same-length members."""
  open_groups, order = {}, []
  for i, (prompt, _) in enumerate(workload):
    g = open_groups.setdefault(len(prompt), [])
    g.append((i, prompt))
    if len(g) >= slots:
      order.append(open_groups.pop(len(prompt)))
  order.extend(g for g in open_groups.values() if g)
  # completion order: a group finishes when its LAST member arrived
  order.sort(key=lambda g: g[-1][0])
  return order


def run_static_pass(params, cfg, groups, num_steps, eos_id):
  """One static pass; returns (wall_s, per-request latencies)."""
  import numpy as np
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import transformer as tfm

  def run_group(group):
    prompts = jnp.asarray(np.stack([p for _, p in group]))
    return tfm.greedy_generate_kv(params, cfg, prompts, num_steps,
                                  eos_id=eos_id, pad_id=0)

  t0 = time.perf_counter()
  latencies = []
  for g in groups:
    jax.block_until_ready(run_group(g))
    done_at = time.perf_counter() - t0
    latencies.extend([done_at] * len(g))
  return time.perf_counter() - t0, latencies


def run_continuous_pass(eng, workload):
  """One engine pass; returns (wall_s, latencies, outputs, stat deltas).

  The stats dict is mutated by the engine's loop thread while we read it
  — deltas go through the one snapshot-subtract helper (obs.metrics)."""
  snap = eng.stats_snapshot()
  t0 = time.perf_counter()
  rids = [eng.submit(p, max_new_tokens=b) for p, b in workload]
  reqs = [eng.request(r) for r in rids]
  outs = [eng.result(r, timeout=600) for r in rids]
  wall = time.perf_counter() - t0
  delta = snap.delta()
  return wall, [r.latency for r in reqs], outs, delta


def measure_compare(params, cfg, workload, slots, eos_id, useful, horizon,
                    reps):
  """Paired static/continuous reps (the feed_bench methodology: this box
  throttles minute-to-minute, so each rep measures both modes
  back-to-back and the MEDIAN-speedup rep is reported)."""
  import numpy as np
  from tensorflowonspark_tpu.serving import ServingEngine

  num_steps = max(b for _, b in workload)
  groups = _static_groups(workload, slots)
  total_useful = float(sum(len(s) for s in useful))

  # warm every shape once: static group shapes, engine prefill buckets +
  # fused step (the SAME engine serves every timed rep)
  run_static_pass(params, cfg, groups, num_steps, eos_id)
  eng = ServingEngine(params, cfg, num_slots=slots, eos_id=eos_id,
                      pad_id=0, horizon=horizon).start()
  rows = []
  try:
    run_continuous_pass(eng, workload)
    for _ in range(reps):
      s_wall, s_lat = run_static_pass(params, cfg, groups, num_steps,
                                      eos_id)
      c_wall, c_lat, outs, delta = run_continuous_pass(eng, workload)
      mismatches = 0
      for (prompt, _), out, ref in zip(workload, outs, useful):
        if not np.array_equal(out, np.concatenate([prompt, ref])):
          mismatches += 1
      s_pct, s_agree = _lat_stats(s_lat)
      c_pct, c_agree = _lat_stats(c_lat)
      rows.append({
          "static": dict({
              "tok_s": round(total_useful / s_wall, 2),
              "wall_s": round(s_wall, 3),
              "fixed_steps": num_steps,
              "batches": len(groups),
          }, **s_pct),
          "continuous": dict({
              "tok_s": round(total_useful / c_wall, 2),
              "wall_s": round(c_wall, 3),
              "occupancy": round(
                  delta["live_slot_steps"]
                  / float(max(1, delta["steps"]) * slots), 3),
              "decode_steps": delta["steps"],
              "horizon": horizon,
              "parity_mismatches": mismatches,
          }, **c_pct),
          "sketch_agreement": bool(s_agree and c_agree),
          "speedup": round((total_useful / c_wall)
                           / max(1e-9, total_useful / s_wall), 2),
      })
  finally:
    eng.stop()
  rows.sort(key=lambda r: r["speedup"])
  median = rows[len(rows) // 2]
  median = dict(median, per_rep_speedups=[r["speedup"] for r in rows],
                parity_ok=all(r["continuous"]["parity_mismatches"] == 0
                              for r in rows),
                sketch_agreement_ok=all(r["sketch_agreement"]
                                        for r in rows))
  return median


# --- prefix-heavy workload: the decode-speed stack (--prefix-workload) ------

#: prefix-workload shapes (full, smoke): N distinct system prompts ×
#: Zipf fan-out, short tails, short budgets — the workload shape the
#: paged-KV + prefix-cache + speculative stack exists for. The HBM
#: budget is the CONTIGUOUS reservation of base_slots × max_seq tokens;
#: the paged legs spend the same budget as num_pages pages and convert
#: the headroom into extra concurrent slots (slots_at_equal_hbm).
_PREFIX_FULL = dict(layers=3, heads=4, d_model=128, d_ff=256, vocab=512,
                    requests=48, prefixes=4, prefix_len=96,
                    tail_lens=(2, 4, 6, 8), budgets=(8, 16, 24, 32),
                    max_seq=160, horizon=12, page=8, base_slots=5,
                    paged_slots=10, prefix_pages=48, spec_depth=6,
                    spec_layers=1)
_PREFIX_SMOKE = dict(layers=2, heads=2, d_model=32, d_ff=64, vocab=64,
                     requests=10, prefixes=2, prefix_len=12,
                     tail_lens=(2, 3, 4), budgets=(3, 5), max_seq=32,
                     horizon=4, page=4, base_slots=3, paged_slots=5,
                     prefix_pages=8, spec_depth=2, spec_layers=0)


def _soften_exit_layers(params, num_layers, spec_layers, scale=0.005):
  """Scale the residual contributions of the layers PAST the draft's
  shallow exit toward zero. A randomly initialized network has no layer
  redundancy — every layer flips the argmax, so a self-draft would
  measure noise (~1/vocab acceptance), not the mechanism. A converged
  network is the opposite (late layers refine, rarely overturn — the
  premise shallow-exit drafting rests on); scaling the exit layers'
  out/down projections emulates that regime, the same isolate-the-
  mechanism move as ``measure_speculative``'s draft==target self-bench.
  The measured ``spec_accept_rate`` rides the JSON either way, and the
  parity oracle uses the SAME softened params, so bit-parity stays a
  real check."""
  from jax.tree_util import tree_map_with_path
  deep = {"layer_%d" % i for i in range(spec_layers, num_layers)}

  def f(path, leaf):
    keys = [str(getattr(p, "key", "")) for p in path]
    if deep & set(keys) and len(keys) >= 2 and keys[-1] == "kernel" \
        and keys[-2] in ("out", "down"):
      return leaf * scale
    return leaf

  return tree_map_with_path(f, params)


def make_prefix_workload(shape, seed):
  """Seeded shared-system-prompt workload: ``prefixes`` distinct
  prefix token blocks, each request = Zipf-drawn prefix + short random
  tail (so prompts share long prefixes but diverge, exercising the
  copy-on-write boundary)."""
  import numpy as np
  rng = np.random.RandomState(seed)
  prefixes = [rng.randint(0, shape["vocab"],
                          (shape["prefix_len"],)).astype(np.int32)
              for _ in range(shape["prefixes"])]
  reqs = []
  for _ in range(shape["requests"]):
    pi = _zipf_pick(rng, list(range(shape["prefixes"])))
    tail = rng.randint(
        0, shape["vocab"],
        (_zipf_pick(rng, sorted(shape["tail_lens"])),)).astype(np.int32)
    budget = _zipf_pick(rng, sorted(shape["budgets"]))
    reqs.append((np.concatenate([prefixes[pi], tail]), int(budget)))
  return reqs


def _equal_hbm_pages(shape):
  """The paged pool spending the SAME HBM as base_slots contiguous
  slots (+1 for the trash page) — the one definition both the engine
  configs and the reported slots_at_equal_hbm use, so the artifact can
  never claim a pool the engines didn't run."""
  return shape["base_slots"] * shape["max_seq"] // shape["page"] + 1


#: the staged engine configs: every leg after baseline adds ONE stage,
#: so each stage's parity AND contribution are gated independently
def _prefix_legs(shape):
  paged = dict(num_slots=shape["paged_slots"], page_size=shape["page"],
               num_pages=_equal_hbm_pages(shape))
  return [
      ("baseline", dict(num_slots=shape["base_slots"])),
      ("paged", dict(paged)),
      ("paged_prefix", dict(paged, prefix_pages=shape["prefix_pages"])),
      ("full_stack", dict(paged, prefix_pages=shape["prefix_pages"],
                          spec_depth=shape["spec_depth"],
                          spec_layers=shape.get("spec_layers", 0))),
  ]


def measure_prefix(params, cfg, workload, shape, eos_id, useful, reps):
  """Paired per-rep passes over every leg through PERSISTENT engines
  (shared jit warm across reps; the median-by-stack-speedup rep is
  reported). Stat deltas ride ``stats_snapshot`` — the one
  snapshot-subtract helper — never raw dict copies."""
  import numpy as np
  from tensorflowonspark_tpu.serving import ServingEngine

  total_useful = float(sum(len(s) for s in useful))
  engines = {}
  rows = []
  try:
    for name, kw in _prefix_legs(shape):
      engines[name] = ServingEngine(
          params, cfg, eos_id=eos_id, pad_id=0,
          horizon=shape["horizon"], **kw).start()
      run_continuous_pass(engines[name], workload)    # warm every shape
    for _ in range(reps):
      legs = {}
      for name, _kw in _prefix_legs(shape):
        eng = engines[name]
        wall, lats, outs, delta = run_continuous_pass(eng, workload)
        mismatches = sum(
            1 for (prompt, _), out, ref in zip(workload, outs, useful)
            if not np.array_equal(out, np.concatenate([prompt, ref])))
        pct, _ = _lat_stats(lats)
        leg = dict({
            "tok_s": round(total_useful / wall, 2),
            "wall_s": round(wall, 3),
            "prefills": int(delta["prefills"]),
            "parity_mismatches": mismatches,
        }, **pct)
        if eng.page_size:
          leg["prefix_hits"] = int(delta["prefix_hits"])
          leg["prefix_evictions"] = int(delta["prefix_evictions"])
          leg["kv_pages_in_use"] = eng.kv_pages_in_use
        if eng.spec_depth:
          acc, rej = delta["spec_accepted"], delta["spec_rejected"]
          leg["spec_accept_rate"] = round(acc / max(1.0, acc + rej), 3)
        legs[name] = leg
      base = legs["baseline"]["tok_s"]
      rows.append({
          "legs": legs,
          "speedup_by_leg": {n: round(legs[n]["tok_s"] / max(1e-9, base),
                                      2) for n in legs},
      })
  finally:
    for eng in engines.values():
      eng.stop()
  rows.sort(key=lambda r: r["speedup_by_leg"]["full_stack"])
  return rows[len(rows) // 2], rows


def run_prefix(args):
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import transformer as tfm

  shape = _PREFIX_SMOKE if args.smoke else _PREFIX_FULL
  if args.requests:
    shape = dict(shape, requests=args.requests)
  cfg = tfm.TransformerConfig(
      vocab_size=shape["vocab"], num_layers=shape["layers"],
      num_heads=shape["heads"], d_model=shape["d_model"],
      d_ff=shape["d_ff"], max_seq_len=shape["max_seq"], remat=False,
      dtype=jnp.float32)   # f32: the bit-parity check must be exact
  state = tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=16)
  spec_layers = shape.get("spec_layers", 0) or max(1, shape["layers"] // 2)
  params = _soften_exit_layers(state.params, shape["layers"], spec_layers)
  eos_id = 2
  workload = make_prefix_workload(shape, args.seed)
  useful = _reference_streams(params, cfg, workload, eos_id)
  reps = args.reps if args.reps else (1 if args.smoke else 3)
  median, rows = measure_prefix(params, cfg, workload, shape,
                                eos_id, useful, reps)
  num_pages = _equal_hbm_pages(shape)
  parity_ok = all(leg["parity_mismatches"] == 0
                  for r in rows for leg in r["legs"].values())
  result = {
      "metric": "serving_prefix_stack_tokens_per_sec",
      "mode": "smoke" if args.smoke else "full",
      "seed": args.seed, "reps": reps,
      "workload": {
          "requests": shape["requests"], "prefixes": shape["prefixes"],
          "prefix_len": shape["prefix_len"],
          "tail_lens": list(shape["tail_lens"]),
          "budgets": list(shape["budgets"]),
          "useful_tokens": int(sum(len(s) for s in useful)),
      },
      "model": {k: shape[k] for k in ("layers", "heads", "d_model",
                                      "d_ff", "vocab", "max_seq")},
      "hbm_budget_tokens": shape["base_slots"] * shape["max_seq"],
      "slots_at_equal_hbm": {"contiguous": shape["base_slots"],
                             "paged": shape["paged_slots"],
                             "num_pages": num_pages,
                             "page_size": shape["page"]},
      "legs": median["legs"],
      "speedup_by_leg": median["speedup_by_leg"],
      "speedup": median["speedup_by_leg"]["full_stack"],
      "per_rep_stack_speedups": [r["speedup_by_leg"]["full_stack"]
                                 for r in rows],
      "parity_ok": parity_ok,
      "note": "N distinct system prompts × Zipf fan-out; same seeded "
              "workload through four persistent engines — baseline = "
              "the PR 10 contiguous engine at the HBM budget's slot "
              "count; each later leg adds one stage (paged KV at equal "
              "HBM → more slots, shared-prefix cache, self-speculative "
              "decode). tokens/sec counts useful tokens only; every "
              "leg's outputs verified bit-identical to single-request "
              "decodes (the per-stage parity gate). The model's exit "
              "layers are scaled toward identity to emulate a trained "
              "network's layer redundancy (_soften_exit_layers) — "
              "random weights would measure ~1/vocab draft acceptance, "
              "noise instead of the mechanism; spec_accept_rate carries "
              "what was actually accepted",
  }
  line = json.dumps(result)
  if args.json_out:
    with open(args.json_out, "w") as f:
      f.write(line + "\n")
    from tools import bench_history
    bench_history.append_record(
        "serve_bench_prefix", result["legs"]["full_stack"]["tok_s"],
        "%s-r%d-p%dx%d-seed%d" % (result["mode"], shape["requests"],
                                  shape["prefixes"], shape["prefix_len"],
                                  args.seed),
        extra={"speedup": result["speedup"],
               "speedup_by_leg": result["speedup_by_leg"]})
  print(line)
  return 0 if parity_ok else 3


# --- fleet mode: replica router vs single engine (--fleet) ------------------

#: fleet-mode shapes (full, smoke): the single-engine leg serves the
#: workload on ``slots`` slots; the fleet leg runs ``replicas`` engines
#: of the SAME slot count behind the ServingFleet router with a rolling
#: param swap fired mid-run — the claim under test is the ROUTER's
#: (load-aware dispatch + zero-shed swap), not raw decode speed
_FLEET_FULL = dict(layers=2, heads=4, d_model=128, d_ff=256, vocab=512,
                   requests=48, slots=4, replicas=3,
                   plens=(4, 8, 12, 16), budgets=(8, 16, 32, 64),
                   max_seq=96, horizon=8)
_FLEET_SMOKE = dict(layers=2, heads=2, d_model=32, d_ff=64, vocab=64,
                    requests=10, slots=2, replicas=2, plens=(4, 6, 8),
                    budgets=(4, 8), max_seq=24, horizon=4)


def _warm_engine(eng, workload):
  """Warm one engine's jit caches (one request per distinct prompt
  length covers the prefill bucket decompositions; any request warms the
  fused step) — the canary pattern: a swap-in replica is warmed BEFORE
  it takes traffic, so the timed pass measures the drain/handoff, not
  XLA compiles."""
  seen, probe = set(), []
  for p, b in workload:
    if len(p) not in seen:
      seen.add(len(p))
      probe.append((p, b))
  eng.start()
  eng.generate([p for p, _ in probe],
               max_new_tokens=max(b for _, b in probe), timeout=600)


def run_fleet_pass(fleet, workload, swap_factory=None, swap_timeout=600.0):
  """One fleet pass; optionally fires a rolling swap mid-run (requests
  are in flight when the first replica starts draining). Returns
  (wall_s, latencies, outputs, stats delta, swap report)."""
  snap = fleet.stats_snapshot()
  t0 = time.perf_counter()
  frids = [fleet.submit(p, max_new_tokens=b) for p, b in workload]
  reqs = [fleet.request(fr) for fr in frids]
  swap = None
  if swap_factory is not None:
    swap = fleet.rolling_swap(timeout=swap_timeout,
                              engine_factory=swap_factory)
  outs = [fleet.result(fr, timeout=600) for fr in frids]
  wall = time.perf_counter() - t0
  return wall, [r.latency for r in reqs], outs, snap.delta(), swap


def measure_fleet(params, cfg, workload, shape, eos_id, useful, reps):
  """Paired single-engine vs fleet reps (median-by-speedup reported).
  Every rep's fleet pass includes a full rolling swap to PRE-WARMED
  replacement engines; parity, zero-shed and swap completion are gated
  per rep."""
  import numpy as np
  from tensorflowonspark_tpu.serving import ServingEngine, ServingFleet

  slots, replicas = shape["slots"], shape["replicas"]
  total_useful = float(sum(len(s) for s in useful))

  def factory():
    return ServingEngine(params, cfg, num_slots=slots, eos_id=eos_id,
                         pad_id=0, horizon=shape["horizon"])

  single = factory().start()
  fleet = ServingFleet(factory, num_replicas=replicas).start()
  rows = []
  spares = []
  try:
    run_continuous_pass(single, workload)          # warm the single leg
    run_fleet_pass(fleet, workload)                # warm every replica
    for _ in range(reps):
      spares = [factory() for _ in range(replicas)]
      for eng in spares:
        _warm_engine(eng, workload)
      s_wall, s_lat, s_outs, _ = run_continuous_pass(single, workload)
      f_wall, f_lat, f_outs, delta, swap = run_fleet_pass(
          fleet, workload, swap_factory=lambda: spares.pop(0))
      mismatches = sum(
          1 for (prompt, _), out, ref in zip(workload, f_outs, useful)
          if not np.array_equal(out, np.concatenate([prompt, ref])))
      s_pct, _ = _lat_stats(s_lat)
      f_pct, _ = _lat_stats(f_lat)
      rows.append({
          "single": dict({
              "tok_s": round(total_useful / s_wall, 2),
              "wall_s": round(s_wall, 3),
          }, **s_pct),
          "fleet": dict({
              "tok_s": round(total_useful / f_wall, 2),
              "wall_s": round(f_wall, 3),
              **f_pct,
              "dispatched": int(delta.get("dispatched", 0)),
              "retries": int(delta.get("retries", 0)),
              "failovers": int(delta.get("failovers", 0)),
              "shed": int(delta.get("shed", 0)),
              "swaps": int(delta.get("swaps", 0)),
              "replay_mismatches":
                  int(delta.get("replay_mismatches", 0)),
              "swap_drained_all": bool(
                  swap and all(r.get("drained")
                               for r in swap["replicas"]
                               if "drained" in r)),
              "parity_mismatches": mismatches,
          }),
          "speedup": round((total_useful / f_wall)
                           / max(1e-9, total_useful / s_wall), 2),
      })
  finally:
    single.stop()
    fleet.stop()
    for eng in spares:
      eng.stop()
  rows.sort(key=lambda r: r["speedup"])
  return rows[len(rows) // 2], rows


def run_fleet(args):
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import transformer as tfm

  shape = _FLEET_SMOKE if args.smoke else _FLEET_FULL
  if args.requests:
    shape = dict(shape, requests=args.requests)
  if args.slots:
    shape = dict(shape, slots=args.slots)
  if args.replicas:
    shape = dict(shape, replicas=args.replicas)
  cfg = tfm.TransformerConfig(
      vocab_size=shape["vocab"], num_layers=shape["layers"],
      num_heads=shape["heads"], d_model=shape["d_model"],
      d_ff=shape["d_ff"], max_seq_len=shape["max_seq"], remat=False,
      dtype=jnp.float32)   # f32: the bit-parity check must be exact
  state = tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=16)
  eos_id = 2
  workload = make_workload(shape, args.seed)
  useful = _reference_streams(state.params, cfg, workload, eos_id)
  reps = args.reps if args.reps else (1 if args.smoke else 2)
  median, rows = measure_fleet(state.params, cfg, workload, shape,
                               eos_id, useful, reps)
  zero_shed = all(r["fleet"]["shed"] == 0 and
                  r["fleet"]["swaps"] == shape["replicas"]
                  for r in rows)
  parity_ok = all(r["fleet"]["parity_mismatches"] == 0 and
                  r["fleet"]["replay_mismatches"] == 0 for r in rows)
  result = {
      "metric": "serving_fleet_vs_single_tokens_per_sec",
      "mode": "smoke" if args.smoke else "full",
      "seed": args.seed, "reps": reps,
      "workload": {"requests": shape["requests"], "slots": shape["slots"],
                   "replicas": shape["replicas"],
                   "useful_tokens": int(sum(len(s) for s in useful))},
      "model": {k: shape[k] for k in ("layers", "heads", "d_model",
                                      "d_ff", "vocab", "max_seq")},
      "single": median["single"],
      "fleet": median["fleet"],
      "speedup": median["speedup"],
      "per_rep_speedups": [r["speedup"] for r in rows],
      "zero_shed": zero_shed,
      "parity_ok": parity_ok,
      "note": "same seeded Zipf workload through one engine vs a "
              "ServingFleet of N same-shape replicas with a FULL "
              "rolling param swap fired mid-run (every replica drained "
              "and replaced while requests were in flight; swap-in "
              "engines pre-warmed — the canary pattern — so the pass "
              "prices the drain/handoff, not XLA compiles). "
              "zero_shed requires every accepted request to complete "
              "and all replicas to swap; parity_ok requires every "
              "fleet output bit-identical to its single-request "
              "decode with zero cross-replica replay mismatches. "
              "On a 2-vCPU box the replicas' loop threads contend for "
              "the same cores, so the speedup understates what "
              "N-executor deployment delivers — the gated claims are "
              "parity and zero-shed, not the ratio",
  }
  line = json.dumps(result)
  if args.json_out:
    with open(args.json_out, "w") as f:
      f.write(line + "\n")
    from tools import bench_history
    bench_history.append_record(
        "serve_bench_fleet", result["fleet"]["tok_s"],
        "%s-r%d-s%d-n%d-seed%d" % (result["mode"], shape["requests"],
                                   shape["slots"], shape["replicas"],
                                   args.seed),
        extra={"speedup": result["speedup"],
               "zero_shed": zero_shed})
  print(line)
  return 0 if (parity_ok and zero_shed) else 3


# --- cross-host fleet mode (--fleet --cross-host) ---------------------------

#: sync rounds WITH requests in flight before the chaos kill fires on
#: the target host — the ``decode`` point only ticks while the host
#: holds live requests, so this lands mid-decode on every machine
#: whatever the engine build/jit-warm phases cost (utils/chaos.py)
_XHOST_KILL_NTH = 25


def _run_xhost_swap_pass(fleet, workload, factory, version):
  """Submit the workload, fire a rolling swap ACROSS the process
  boundary while those requests are in flight (each host drains, frees
  its reservation, and the replacement proxy rebuilds the commanded
  registry version on it), then collect. Returns
  (outs, stats delta, swap report)."""
  snap = fleet.stats_snapshot()
  frids = [fleet.submit(p, max_new_tokens=b) for p, b in workload]
  swap = fleet.rolling_swap(timeout=600.0, engine_factory=factory,
                            version=version)
  outs = [fleet.result(fr, timeout=600) for fr in frids]
  return outs, snap.delta(), swap


def run_fleet_xhost(args):
  """Paired in-process vs CROSS-HOST fleet, then a chaos kill leg.

  Leg L: ServingFleet over in-process engines (the PR 12 baseline).
  Leg X: the SAME fleet code over RemoteReplica proxies whose engines
  live in spawned ServingHost executor processes behind the rendezvous
  wire — parity + a mid-run rolling swap (v1→v2 through the registry,
  cross-process) gated zero-shed. Leg C: fresh chaos-armed hosts; the
  first host SIGKILLs itself mid-decode (``TOS_CHAOS_HOST``) — the
  fleet must eject it, failover-replay bit-identically, and a
  subsequent rolling swap across the process boundary must shed zero.
  """
  import tempfile
  import numpy as np
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu.control import rendezvous
  from tensorflowonspark_tpu.models import transformer as tfm
  from tensorflowonspark_tpu.serving import (
      ModelRegistry, ServingEngine, ServingFleet)
  from tensorflowonspark_tpu.serving import host as host_mod
  from tensorflowonspark_tpu.serving import remote as remote_mod
  from tensorflowonspark_tpu.utils import chaos

  if jax.default_backend() == "tpu":
    # one process per chip: this process holds the chip (reference
    # decodes, the in-process leg) and then spawns host processes that
    # need the same one — they would fail or hang inside libtpu
    sys.exit("serve_bench --fleet --cross-host cannot run on a TPU as it "
             "stands: this process takes the chip and its spawned hosts "
             "need it too (one process per chip). Run it with "
             "JAX_PLATFORMS=cpu; the chip version is ROADMAP S1/S7 "
             "(thread-mode hosts, or legs in children of a JAX-free "
             "parent)")

  shape = _FLEET_SMOKE if args.smoke else _FLEET_FULL
  if args.requests:
    shape = dict(shape, requests=args.requests)
  if args.replicas:
    shape = dict(shape, replicas=args.replicas)
  replicas = shape["replicas"]
  cfg = tfm.TransformerConfig(
      vocab_size=shape["vocab"], num_layers=shape["layers"],
      num_heads=shape["heads"], d_model=shape["d_model"],
      d_ff=shape["d_ff"], max_seq_len=shape["max_seq"], remat=False,
      dtype=jnp.float32)   # f32: the bit-parity gates must be exact
  eos_id = 2
  state = tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=16)
  workload = make_workload(shape, args.seed)
  useful = _reference_streams(state.params, cfg, workload, eos_id)
  total_useful = float(sum(len(s) for s in useful))
  refs = [np.concatenate([p, r]) for (p, _), r in zip(workload, useful)]

  def mismatches(outs):
    return sum(1 for o, r in zip(outs, refs)
               if o is None or o.shape != r.shape or not bool((o == r).all()))

  serve_opts = dict(num_slots=shape["slots"], eos_id=eos_id, pad_id=0,
                    horizon=shape["horizon"])
  host_timeout = 180.0
  t0 = time.perf_counter()
  server = rendezvous.Server(count=1)
  addr = server.start()
  plane = remote_mod.attach_serving_plane(server)
  probe = remote_mod.wire_health_probe(addr)
  procs = []
  with tempfile.TemporaryDirectory(prefix="tos-xhost-registry-") as root:
    reg = ModelRegistry(root)
    # v2 republishes the SAME params at a later step: the swap leg must
    # be output-invariant, so parity stays the one gate for everything
    extra = {"model_cfg": host_mod.cfg_wire(cfg), "serve_opts": serve_opts}
    v1 = reg.publish(state.params, step=100, extra=extra)
    v2 = reg.publish(state.params, step=200, extra=extra)
    try:
      # ---- leg L: in-process fleet (the wire-free baseline) ----------------
      lfleet = ServingFleet(
          lambda: ServingEngine(state.params, cfg, **serve_opts),
          num_replicas=replicas).start()
      try:
        if not args.smoke:
          run_fleet_pass(lfleet, workload)           # warm the jit caches
        l_wall, _, l_outs, l_delta, _ = run_fleet_pass(lfleet, workload)
      finally:
        lfleet.stop()

      # ---- leg X: the same fleet over executor-resident hosts --------------
      for hid in range(replicas):
        procs.append(host_mod.start_host_process(addr, hid,
                                                 registry_root=root))
      plane.await_hosts(replicas, timeout=host_timeout)
      xfleet = ServingFleet(
          remote_mod.remote_engine_factory(plane, version=v1),
          num_replicas=replicas, health_probe=probe).start()
      try:
        for rid in xfleet.replica_states():
          xfleet.set_replica_version(rid, v1)
        if not args.smoke:
          run_fleet_pass(xfleet, workload)
        x_wall, _, x_outs, x_delta, _ = run_fleet_pass(xfleet, workload)
        swap_outs, swap_delta, swap = _run_xhost_swap_pass(
            xfleet, workload,
            remote_mod.remote_engine_factory(plane, version=v2), v2)
        swap_versions = set(xfleet.served_versions().values())
      finally:
        xfleet.stop()
      # retire leg-X hosts so leg C's chaos-armed processes are the only
      # live hosts the plane can hand out
      for hid in range(replicas):
        plane.enqueue(hid, {"op": "exit"})
      for p in procs:
        p.join(timeout=30)

      # ---- leg C: kill one host mid-decode (TOS_CHAOS_HOST) ----------------
      kill_target = 100
      chaos_env = {chaos.ENV_HOST:
                   "decode@%d#%d:kill" % (kill_target, _XHOST_KILL_NTH)}
      cprocs = [host_mod.start_host_process(addr, kill_target + i,
                                            registry_root=root,
                                            env=chaos_env)
                for i in range(replicas)]
      procs.extend(cprocs)
      plane.await_hosts(replicas, timeout=host_timeout)
      cfleet = ServingFleet(
          remote_mod.remote_engine_factory(plane, version=v1),
          num_replicas=replicas, health_probe=probe).start()
      try:
        csnap = cfleet.stats_snapshot()
        # no warm pass: the kill must land in a pass with real traffic
        c_frids = [cfleet.submit(p, max_new_tokens=b) for p, b in workload]
        c_outs = [cfleet.result(fr, timeout=600) for fr in c_frids]
        c_delta = csnap.delta()
        cprocs[0].join(timeout=60)
        killed = cprocs[0].exitcode == -9          # SIGKILL, not a clean exit
        ejected = "ejected" in cfleet.replica_states().values()
        # the post-kill rolling swap: survivors drain + rebuild v2 across
        # the process boundary with requests in flight, shedding nothing
        postswap_outs, postswap_delta, postswap = _run_xhost_swap_pass(
            cfleet, workload,
            remote_mod.remote_engine_factory(plane, version=v2), v2)
      finally:
        cfleet.stop()
    finally:
      for hid in plane.host_ids():
        plane.enqueue(hid, {"op": "exit"})
      for p in procs:
        p.join(timeout=15)
        if p.is_alive():
          p.terminate()
      server.stop()
  wall = time.perf_counter() - t0

  parity_ok = (mismatches(l_outs) == 0 and mismatches(x_outs) == 0
               and mismatches(swap_outs) == 0 and mismatches(c_outs) == 0
               and mismatches(postswap_outs) == 0)
  zero_shed = all(int(d.get("shed", 0)) == 0 and
                  int(d.get("replay_mismatches", 0)) == 0
                  for d in (l_delta, x_delta, swap_delta, c_delta,
                            postswap_delta))
  swap_ok = (swap["swapped"] == replicas
             and all(r.get("drained") for r in swap["replicas"])
             and swap_versions == {v2})
  chaos_ok = (killed and ejected
              and int(c_delta.get("failovers", 0)) >= 1
              and int(c_delta.get("ejections", 0)) >= 1
              and postswap["swapped"] == replicas - 1
              and all(r.get("drained") for r in postswap["replicas"]
                      if "drained" in r))
  ok = parity_ok and zero_shed and swap_ok and chaos_ok
  result = {
      "metric": "serving_fleet_cross_host_vs_in_process_tokens_per_sec",
      "mode": "smoke" if args.smoke else "full",
      "seed": args.seed, "wall_s": round(wall, 3),
      "workload": {"requests": shape["requests"], "slots": shape["slots"],
                   "replicas": replicas,
                   "useful_tokens": int(total_useful)},
      "model": {k: shape[k] for k in ("layers", "heads", "d_model",
                                      "d_ff", "vocab", "max_seq")},
      "in_process": {"tok_s": round(total_useful / l_wall, 2),
                     "wall_s": round(l_wall, 3)},
      "cross_host": {"tok_s": round(total_useful / x_wall, 2),
                     "wall_s": round(x_wall, 3),
                     "dispatched": int(x_delta.get("dispatched", 0)),
                     "retries": int(x_delta.get("retries", 0)),
                     "plane": dict(plane.stats)},
      "wire_relative": round((total_useful / x_wall)
                             / max(1e-9, total_useful / l_wall), 3),
      "swap": {"swapped": swap["swapped"],
               "versions_after": sorted(swap_versions),
               "shed": int(swap_delta.get("shed", 0))},
      "chaos": {"killed_host": kill_target, "sigkilled": killed,
                "ejected": ejected,
                "failovers": int(c_delta.get("failovers", 0)),
                "ejections": int(c_delta.get("ejections", 0)),
                "replays": int(c_delta.get("replays", 0)),
                "shed": int(c_delta.get("shed", 0)),
                "post_kill_swapped": postswap["swapped"]},
      "parity_ok": parity_ok, "zero_shed": zero_shed,
      "swap_ok": swap_ok, "chaos_ok": chaos_ok,
      "note": "the SAME ServingFleet code routed over in-process engines "
              "vs RemoteReplica proxies whose engines run in spawned "
              "ServingHost executor processes behind the rendezvous wire "
              "(SHREG/SHSYNC framing, registry-built models). Gates: "
              "bit-parity on every leg (including the v1->v2 rolling "
              "swap ACROSS the process boundary and the chaos leg where "
              "TOS_CHAOS_HOST SIGKILLs a host mid-decode: ejection + "
              "failover replay + a post-kill zero-shed swap), zero shed "
              "and zero replay mismatches everywhere. wire_relative "
              "under 1.0 is the wire+sync tax; on one box all host "
              "processes share the same cores, so it understates "
              "N-executor deployment",
  }
  line = json.dumps(result)
  if args.json_out:
    with open(args.json_out, "w") as f:
      f.write(line + "\n")
    from tools import bench_history
    bench_history.append_record(
        "serve_bench_fleet_xhost", result["cross_host"]["tok_s"],
        "%s-r%d-s%d-n%d-seed%d" % (result["mode"], shape["requests"],
                                   shape["slots"], replicas, args.seed),
        extra={"wire_relative": result["wire_relative"],
               "parity_ok": parity_ok, "zero_shed": zero_shed,
               "chaos_ok": chaos_ok})
  print(line)
  return 0 if ok else 3


# --- deploy mode: continuous train→serve rollout under chaos (--deploy) -----

#: deploy-mode shapes: a registry with a baseline version serving on a
#: fleet, then (leg A) a candidate driven CANARY→VERIFY→PROMOTE with the
#: controller chaos-KILLED at the first promote boundary — resume() must
#: converge every replica to ONE version with zero shed and v2-parity
#: outputs — and (leg B) a POISONED candidate VERIFY must catch, roll
#: back bit-identically and quarantine
_DEPLOY_FULL = dict(layers=2, heads=4, d_model=128, d_ff=256, vocab=512,
                    requests=24, slots=4, replicas=3,
                    plens=(4, 8, 12), budgets=(8, 16, 32),
                    max_seq=96, horizon=8)
_DEPLOY_SMOKE = dict(layers=2, heads=2, d_model=32, d_ff=64, vocab=64,
                     requests=8, slots=2, replicas=2, plens=(4, 6, 8),
                     budgets=(4, 8), max_seq=24, horizon=4)


def run_deploy(args):
  import numpy as np
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import transformer as tfm
  from tensorflowonspark_tpu.serving import (
      ControllerKilled, DeploymentController, ModelRegistry,
      ServingEngine, ServingFleet)
  from tensorflowonspark_tpu.utils import chaos

  shape = _DEPLOY_SMOKE if args.smoke else _DEPLOY_FULL
  if args.requests:
    shape = dict(shape, requests=args.requests)
  if args.replicas:
    shape = dict(shape, replicas=args.replicas)
  cfg = tfm.TransformerConfig(
      vocab_size=shape["vocab"], num_layers=shape["layers"],
      num_heads=shape["heads"], d_model=shape["d_model"],
      d_ff=shape["d_ff"], max_seq_len=shape["max_seq"], remat=False,
      dtype=jnp.float32)   # f32: the bit-parity gates must be exact
  eos_id = 2
  # three "training runs": distinct seeds stand in for checkpoints at
  # successive steps — what publish_on_checkpoint would stream out
  states = [tfm.create_state(jax.random.PRNGKey(s), cfg, seq_len=16)
            for s in (0, 1, 2)]
  workload = make_workload(shape, args.seed)
  probe = workload[:3]

  def reference_decode(params, prompt, budget):
    out = np.asarray(tfm.greedy_generate_kv(
        params, cfg, jnp.asarray(prompt)[None], int(budget),
        eos_id=eos_id, pad_id=0))[0]
    gen = out[len(prompt):]
    stops = np.where(gen == eos_id)[0]
    stop = (int(stops[0]) + 1) if len(stops) else int(budget)
    return np.concatenate([np.asarray(prompt), gen[:stop]])

  def make_factory(params, manifest):
    def factory():
      return ServingEngine(params, cfg, num_slots=shape["slots"],
                           eos_id=eos_id, pad_id=0,
                           horizon=shape["horizon"])
    return factory

  import tempfile
  t0 = time.perf_counter()
  with tempfile.TemporaryDirectory(prefix="tos-registry-") as root:
    reg = ModelRegistry(root)
    v1 = reg.publish(states[0].params, step=100)
    v2 = reg.publish(states[1].params, step=200)
    p1, m1 = reg.get(v1)
    fleet = ServingFleet(make_factory(p1, m1),
                         num_replicas=shape["replicas"]).start()
    base_snap = fleet.stats_snapshot()
    try:
      for rid in fleet.replica_states():
        fleet.set_replica_version(rid, v1)
      ctl = DeploymentController(
          fleet, reg, make_factory, reference_decode, probe,
          baseline_version=v1, traffic_slice=0.5,
          bake_seconds=0.3 if args.smoke else 1.5,
          spot_checks=2 if args.smoke else 4, swap_timeout=300.0)

      # ---- leg A: promote v2, controller killed mid-promote ----------------
      os.environ[chaos.ENV_DEPLOY] = "promote:kill"
      chaos.reset()
      killed = False
      try:
        ctl.deploy(v2, bake_traffic=workload)
      except ControllerKilled:
        killed = True
      finally:
        os.environ.pop(chaos.ENV_DEPLOY, None)
        chaos.reset()
      served_mid = dict(fleet.served_versions())
      # the fleet must keep serving THROUGH the partial rollout: drive
      # the full workload against the mixed-version fleet before anyone
      # repairs anything
      mid_frids = [fleet.submit(p, max_new_tokens=b) for p, b in workload]
      mid_outs = [fleet.result(fr, timeout=600) for fr in mid_frids]
      resume_rep = ctl.resume(timeout=300.0)
      served_after = dict(fleet.served_versions())
      version_consistent = (set(served_after.values()) == {v2})
      # post-convergence parity: every output bit-identical to the v2
      # single-request reference decode
      p2, _ = reg.get(v2)
      refs2 = [reference_decode(p2, p, b) for p, b in workload]
      outs2 = [fleet.result(fleet.submit(p, max_new_tokens=b),
                            timeout=600) for p, b in workload]
      promote_parity = all(
          o.shape == r.shape and bool((o == r).all())
          for o, r in zip(outs2, refs2))

      # ---- leg B: poisoned candidate — VERIFY must catch + roll back -------
      v3 = reg.publish(states[2].params, step=300)
      os.environ[chaos.ENV_DEPLOY] = "canary:poison"
      chaos.reset()
      try:
        verdict = ctl.deploy(v3, bake_traffic=workload)
      finally:
        os.environ.pop(chaos.ENV_DEPLOY, None)
        chaos.reset()
      poison_caught = ((not verdict["ok"])
                       and verdict["parity"]["mismatches"] > 0)
      rollback_ok = bool(verdict.get("rollback_bit_identical"))
      quarantined = reg.is_quarantined(v3)
      never_promoted = (reg.latest() == v2
                        and set(fleet.served_versions().values()) == {v2})
      delta = base_snap.delta()
      zero_shed = int(delta.get("shed", 0)) == 0
      completed_mid = sum(1 for o in mid_outs if o is not None)
    finally:
      fleet.stop()
  wall = time.perf_counter() - t0

  ok = (killed and zero_shed and version_consistent and promote_parity
        and poison_caught and rollback_ok and quarantined
        and never_promoted)
  result = {
      "metric": "serving_deploy_canary_rollout",
      "mode": "smoke" if args.smoke else "full",
      "seed": args.seed, "wall_s": round(wall, 3),
      "workload": {"requests": shape["requests"], "slots": shape["slots"],
                   "replicas": shape["replicas"]},
      "model": {k: shape[k] for k in ("layers", "heads", "d_model",
                                      "d_ff", "vocab", "max_seq")},
      "versions": {"baseline": v1, "promoted": v2, "poisoned": v3},
      "killed_mid_promote": killed,
      "served_mid_kill": {str(k): v for k, v in served_mid.items()},
      "completed_during_partial_rollout": completed_mid,
      "resume": resume_rep,
      "version_consistent": version_consistent,
      "promote_parity": promote_parity,
      "poison_caught_by_verify": poison_caught,
      "rollback_bit_identical": rollback_ok,
      "quarantined": quarantined,
      "never_promoted": never_promoted,
      "zero_shed": zero_shed,
      "fleet_counters": {k: int(delta.get(k, 0)) for k in
                         ("dispatched", "shed", "swaps", "failovers",
                          "canary_dispatches")},
      "note": "continuous train→serve rollout under chaos: candidate v2 "
              "driven CANARY→VERIFY→PROMOTE with the controller KILLED "
              "at the first promote boundary (TOS_CHAOS_DEPLOY) — the "
              "mixed-version fleet keeps completing requests, then "
              "resume() converges every replica to v2 with outputs "
              "bit-identical to the v2 reference decode; then poisoned "
              "candidate v3 (params corrupted at the canary build) is "
              "caught by VERIFY's greedy parity spot-checks, rolled "
              "back bit-identically and quarantined. All gates are "
              "hard: killed, zero_shed, version_consistent, "
              "promote_parity, poison_caught, rollback_bit_identical, "
              "quarantined, never_promoted",
  }
  line = json.dumps(result)
  if args.json_out:
    with open(args.json_out, "w") as f:
      f.write(line + "\n")
    from tools import bench_history
    bench_history.append_record(
        "serve_bench_deploy", 1.0 if ok else 0.0,
        "%s-r%d-n%d-seed%d" % (result["mode"], shape["requests"],
                               shape["replicas"], args.seed),
        extra={"zero_shed": zero_shed,
               "version_consistent": version_consistent,
               "poison_caught": poison_caught})
  print(line)
  return 0 if ok else 3


# --- chaos mode: goodput + recovery latency under injected faults -----------

#: deterministic fault schedules for --chaos (TOS_CHAOS_SERVE grammar,
#: utils/chaos.py): decode#N counts fused decode dispatches, so the
#: crashes land mid-run with requests in flight on every seed
_CHAOS_FULL_SPEC = "decode#6:raise,decode#18:raise"
_CHAOS_SMOKE_SPEC = "decode#3:raise"


def run_chaos_pass(eng, workload):
  """One engine pass that tolerates per-request failures; returns
  (wall_s, outputs_or_None, stats delta, failed count)."""
  snap = eng.stats_snapshot()
  t0 = time.perf_counter()
  rids = [eng.submit(p, max_new_tokens=b) for p, b in workload]
  outs, failed = [], 0
  for rid in rids:
    try:
      outs.append(eng.result(rid, timeout=600))
    except Exception as e:  # noqa: BLE001 - a poisoned/failed request is
      # a reportable outcome here, not a bench crash
      sys.stderr.write("chaos pass request failed: %r\n" % (e,))
      outs.append(None)
      failed += 1
  return time.perf_counter() - t0, outs, snap.delta(), failed


def measure_chaos(params, cfg, workload, slots, eos_id, useful, horizon,
                  reps, spec):
  """Paired clean/chaos reps through ONE engine (same jit caches both
  legs); the chaos env is armed only around the chaos leg and the chaos
  invocation counters reset per rep so the same faults re-fire."""
  import numpy as np
  from tensorflowonspark_tpu.serving import ServingEngine
  from tensorflowonspark_tpu.utils import chaos

  # poison_crashes above the injected crash count: the schedule injects
  # infrastructure faults, not poison requests — nobody should be failed
  eng = ServingEngine(params, cfg, num_slots=slots, eos_id=eos_id,
                      pad_id=0, horizon=horizon,
                      poison_crashes=spec.count("raise") + 1).start()
  rows = []
  try:
    run_chaos_pass(eng, workload)          # warm every shape, no faults
    for _ in range(reps):
      c_wall, _, c_delta, c_failed = run_chaos_pass(eng, workload)
      restarts_before = len(eng.restart_log)
      os.environ[chaos.ENV_SERVE] = spec
      chaos.reset()                        # per-rep deterministic counts
      try:
        x_wall, outs, x_delta, x_failed = run_chaos_pass(eng, workload)
      finally:
        del os.environ[chaos.ENV_SERVE]
        chaos.reset()
      recoveries = eng.restart_log[restarts_before:]
      mismatches = sum(
          1 for (prompt, _), out, ref in zip(workload, outs, useful)
          if out is not None and
          not np.array_equal(out, np.concatenate([prompt, ref])))
      total_useful = float(sum(len(s) for s in useful))
      rows.append({
          "clean": {"tok_s": round(total_useful / c_wall, 2),
                    "wall_s": round(c_wall, 3), "failed": c_failed},
          "chaos": {"tok_s": round(total_useful / x_wall, 2),
                    "wall_s": round(x_wall, 3),
                    "restarts": int(x_delta.get("engine_restarts", 0)),
                    "replays": int(x_delta.get("replays", 0)),
                    "poisoned": int(x_delta.get("poisoned", 0)),
                    "replay_mismatches":
                        int(x_delta.get("replay_mismatches", 0)),
                    "failed": x_failed,
                    "parity_mismatches": mismatches},
          "recovery_s": [round(r["duration_s"], 4) for r in recoveries],
          "goodput_ratio": round(c_wall / x_wall, 3),
      })
  finally:
    eng.stop()
  rows.sort(key=lambda r: r["goodput_ratio"])
  return rows[len(rows) // 2], rows


def run_chaos(args):
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import transformer as tfm

  shape = _COMPARE_SMOKE if args.smoke else _COMPARE_FULL
  if args.requests:
    shape = dict(shape, requests=args.requests)
  if args.slots:
    shape = dict(shape, slots=args.slots)
  spec = args.chaos_spec or (_CHAOS_SMOKE_SPEC if args.smoke
                             else _CHAOS_FULL_SPEC)
  cfg = tfm.TransformerConfig(
      vocab_size=shape["vocab"], num_layers=shape["layers"],
      num_heads=shape["heads"], d_model=shape["d_model"],
      d_ff=shape["d_ff"], max_seq_len=shape["max_seq"], remat=False,
      dtype=jnp.float32)   # f32: the bit-parity check must be exact
  state = tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=16)
  eos_id = 2
  workload = make_workload(shape, args.seed)
  useful = _reference_streams(state.params, cfg, workload, eos_id)
  reps = args.reps if args.reps else (1 if args.smoke else 3)
  median, rows = measure_chaos(state.params, cfg, workload,
                               shape["slots"], eos_id, useful,
                               shape["horizon"], reps, spec)
  rec = sorted(s for r in rows for s in r["recovery_s"])
  result = {
      "metric": "serving_chaos_goodput",
      "mode": "smoke" if args.smoke else "full",
      "seed": args.seed, "reps": reps, "chaos_spec": spec,
      "workload": {"requests": shape["requests"], "slots": shape["slots"],
                   "useful_tokens": int(sum(len(s) for s in useful))},
      "clean": median["clean"],
      "chaos": median["chaos"],
      "goodput_ratio": median["goodput_ratio"],
      "per_rep_goodput_ratios": [r["goodput_ratio"] for r in rows],
      "recovery_latency_s": {
          "median": rec[len(rec) // 2] if rec else None,
          "max": rec[-1] if rec else None,
          "events": len(rec)},
      "parity_ok": all(r["chaos"]["parity_mismatches"] == 0 and
                       r["chaos"]["replay_mismatches"] == 0 and
                       r["chaos"]["failed"] == 0 for r in rows),
      "note": "paired clean vs TOS_CHAOS_SERVE-injected passes through "
              "one engine; goodput_ratio = chaos/clean useful tokens/s "
              "(1.0 = free recovery); recovery latency = crash detect "
              "to in-flight replay requeued, incl. backoff "
              "(ServingEngine.restart_log); parity_ok requires every "
              "recovered output bit-identical to its single-request "
              "decode and zero replay mismatches/failures",
  }
  line = json.dumps(result)
  if args.json_out:
    with open(args.json_out, "w") as f:
      f.write(line + "\n")
    from tools import bench_history
    bench_history.append_record(
        "serve_bench_chaos", result["chaos"]["tok_s"],
        "%s-r%d-s%d-h%d-seed%d" % (result["mode"], shape["requests"],
                                   shape["slots"], shape["horizon"],
                                   args.seed),
        extra={"goodput_ratio": result["goodput_ratio"],
               "restarts": result["chaos"]["restarts"]})
  print(line)
  ok = result["parity_ok"] and result["chaos"]["restarts"] >= 1
  return 0 if ok else 3


def run_compare(args):
  import jax
  import jax.numpy as jnp
  import numpy as np
  from tensorflowonspark_tpu.models import transformer as tfm
  from tensorflowonspark_tpu.obs import metrics as obs_metrics

  if obs_metrics.enabled():
    # obs-overhead A/B parity with a real obs-enabled serving process:
    # the compile listener (device tier) must be priced into the "on" leg
    from tensorflowonspark_tpu.obs import device as obs_device
    obs_device.install_compile_listener()

  shape = _COMPARE_SMOKE if args.smoke else _COMPARE_FULL
  if args.requests:
    shape = dict(shape, requests=args.requests)
  if args.slots:
    shape = dict(shape, slots=args.slots)
  cfg = tfm.TransformerConfig(
      vocab_size=shape["vocab"], num_layers=shape["layers"],
      num_heads=shape["heads"], d_model=shape["d_model"],
      d_ff=shape["d_ff"], max_seq_len=shape["max_seq"], remat=False,
      dtype=jnp.float32)   # f32: the bit-parity check must be exact
  state = tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=16)
  eos_id = 2               # whatever the random model emits; both modes
  workload = make_workload(shape, args.seed)       # share the stop rule

  useful = _reference_streams(state.params, cfg, workload, eos_id)
  reps = args.reps if args.reps else (1 if args.smoke else 3)
  median = measure_compare(state.params, cfg, workload, shape["slots"],
                           eos_id, useful, shape["horizon"], reps)
  result = {
      "metric": "serving_continuous_vs_static_tokens_per_sec",
      "mode": "smoke" if args.smoke else "full",
      "seed": args.seed,
      "reps": reps,
      "workload": {
          "requests": shape["requests"], "slots": shape["slots"],
          "prompt_lens": list(shape["plens"]),
          "budgets": list(shape["budgets"]),
          "useful_tokens": int(sum(len(s) for s in useful)),
      },
      "model": {k: shape[k] for k in ("layers", "heads", "d_model",
                                      "d_ff", "vocab", "max_seq")},
      "static": median["static"],
      "continuous": median["continuous"],
      "speedup": median["speedup"],
      "per_rep_speedups": median["per_rep_speedups"],
      "parity_ok": median["parity_ok"],
      # bench and production share ONE percentile estimator
      # (obs.quantiles): the sketch's p50/p99 must agree with the exact
      # sorted list within the sketch's self-reported error bound
      "sketch_agreement_ok": median["sketch_agreement_ok"],
      "note": "same slot count, same seeded Zipf-ish mixed-length "
              "workload; tokens/sec counts each request's useful tokens "
              "(truncated at its own EOS/budget). static = the "
              "fixed-shape make_serving_predict_fn loop: equal-length "
              "batches, fixed num_steps = max budget, batch-at-a-time — "
              "finished rows burn their remaining slot-steps as padding; "
              "continuous = serving.ServingEngine refilling freed slots "
              "mid-flight; engine outputs verified bit-identical to "
              "per-request single decodes",
  }
  line = json.dumps(result)
  if args.json_out:
    with open(args.json_out, "w") as f:
      f.write(line + "\n")
    # bench→history bridge (tools/bench_history.py --check): the engine's
    # useful tokens/s is the headline rate for the regression gate
    from tools import bench_history
    bench_history.append_record(
        "serve_bench", result["continuous"]["tok_s"],
        "%s-r%d-s%d-h%d-seed%d" % (result["mode"],
                                   shape["requests"], shape["slots"],
                                   shape["horizon"], args.seed),
        extra={"speedup": result["speedup"],
               "obs": int(obs_metrics.enabled())})
  print(line)
  ok = result["parity_ok"] and \
      (result["sketch_agreement_ok"] or not args.smoke)
  return 0 if ok else 3


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument("--batch", type=int, default=8)
  ap.add_argument("--prompt", type=int, default=128)
  ap.add_argument("--steps", type=int, default=128)
  ap.add_argument("--configs", default=None,
                  help="comma list of config names to measure (default: "
                       "all)")
  ap.add_argument("--compare", action="store_true",
                  help="continuous (serving.ServingEngine) vs static "
                       "batching on a seeded mixed-length workload")
  ap.add_argument("--chaos", action="store_true",
                  help="paired clean vs fault-injected engine passes: "
                       "degraded goodput + recovery latency under "
                       "TOS_CHAOS_SERVE (parity re-verified)")
  ap.add_argument("--prefix-workload", action="store_true",
                  help="shared-system-prompt workload (N prefixes × "
                       "Zipf fan-out) through the staged decode-speed "
                       "stack: baseline vs paged KV (equal HBM, more "
                       "slots) vs +prefix cache vs +speculative decode")
  ap.add_argument("--fleet", action="store_true",
                  help="ServingFleet of N replicas vs one engine on the "
                       "seeded Zipf workload, with a mid-run rolling "
                       "param swap (parity + zero-shed gated)")
  ap.add_argument("--deploy", action="store_true",
                  help="continuous train→serve rollout drive: registry "
                       "publish → canary → SLO/parity verify → promote "
                       "with a chaos kill mid-promote (resume must "
                       "converge, zero-shed) plus a poisoned candidate "
                       "that VERIFY must quarantine")
  ap.add_argument("--cross-host", action="store_true",
                  help="with --fleet: route the fleet over ServingHost "
                       "EXECUTOR PROCESSES behind the rendezvous wire "
                       "(serving.host/remote) — paired vs in-process, "
                       "with a cross-process rolling swap and a "
                       "TOS_CHAOS_HOST mid-decode kill leg, all "
                       "parity/zero-shed gated")
  ap.add_argument("--replicas", type=int, default=0,
                  help="--fleet/--deploy replica count override")
  ap.add_argument("--chaos-spec", default=None,
                  help="--chaos: override the injected TOS_CHAOS_SERVE "
                       "fault schedule")
  ap.add_argument("--smoke", action="store_true",
                  help="tiny --compare/--chaos shapes for CI")
  ap.add_argument("--requests", type=int, default=0,
                  help="--compare workload size override")
  ap.add_argument("--slots", type=int, default=0,
                  help="--compare slot count override")
  ap.add_argument("--seed", type=int, default=0)
  ap.add_argument("--reps", type=int, default=0,
                  help="--compare paired reps (default 3; smoke 1) — "
                       "median-speedup rep reported")
  ap.add_argument("--json-out", default=None,
                  help="also write the --compare JSON line here")
  args = ap.parse_args()
  from tensorflowonspark_tpu.utils import compile_cache
  compile_cache.setup()                  # this process jits: place the cache
  if args.compare:
    sys.exit(run_compare(args))
  if args.chaos:
    sys.exit(run_chaos(args))
  if args.prefix_workload:
    sys.exit(run_prefix(args))
  if args.fleet:
    sys.exit(run_fleet_xhost(args) if args.cross_host else run_fleet(args))
  if args.deploy:
    sys.exit(run_deploy(args))
  if args.smoke:
    # the per-config modes take their MODEL shape from bench.py, which
    # is fixed at import by TOS_BENCH_SMOKE — a flag can't shrink it
    # retroactively, so refuse a misleading half-smoke
    sys.exit("--smoke shrinks --compare/--chaos/--prefix-workload/"
             "--fleet/--deploy; for the per-config decode modes set "
             "TOS_BENCH_SMOKE=1 instead")
  if os.environ.get("TOS_BENCH_SMOKE"):
    args.batch, args.prompt, args.steps = 2, 16, 16
  wanted = (set(c.strip() for c in args.configs.split(",") if c.strip())
            if args.configs else None)

  # grouped config sized off the model's head count so the smoke shape
  # (4 heads) still exercises a genuinely grouped cache (kv < heads)
  h = _bench.TFM_HEADS
  kv_g = 4 if h % 4 == 0 and h > 4 else max(1, h // 2)
  results = {}
  all_names = ["mha", "gqa%d" % kv_g, "mqa", "gqa%d_kv8" % kv_g,
               "mha_dense_prefill", "spec_self_k4"]
  if wanted is not None:
    unknown = wanted - set(all_names)
    if unknown:
      sys.stderr.write("unknown --configs %s; valid: %s\n"
                       % (sorted(unknown), all_names))
      sys.exit(2)
  for name, kw in (("mha", {}),
                   ("gqa%d" % kv_g, {"num_kv_heads": kv_g}),
                   ("mqa", {"num_kv_heads": 1}),
                   # int8 cache halves the per-step cache reads again on
                   # top of GQA's grouping (decode's HBM bound)
                   ("gqa%d_kv8" % kv_g, {"num_kv_heads": kv_g,
                                         "kv_cache_dtype": "int8"}),
                   # same cache layout as "mha" but prefill pinned to the
                   # dense einsum: the delta vs "mha" (flash prefill on
                   # chip via "auto") isolates the prefill fast path
                   ("mha_dense_prefill", {"attention_impl": "dense"})):
    if wanted is not None and name not in wanted:
      continue
    try:
      tok_s, prefill_ms = measure(kw, args.batch, args.prompt, args.steps)
      results[name] = {"decode_tok_s": round(tok_s, 1),
                       "prefill_ms": round(prefill_ms, 2)}
    except Exception as e:  # noqa: BLE001 - record, keep measuring
      results[name] = {"error": str(e)[:200]}
    sys.stderr.write("serve %s: %r\n" % (name, results[name]))
  if wanted is None or "spec_self_k4" in wanted:
    try:
      results["spec_self_k4"] = {
          "decode_tok_s": round(
              measure_speculative(args.batch, args.prompt, args.steps), 1)}
    except Exception as e:  # noqa: BLE001
      results["spec_self_k4"] = {"error": str(e)[:200]}
    sys.stderr.write("serve spec_self_k4: %r\n"
                     % (results["spec_self_k4"],))
  print(json.dumps({
      "metric": "kv_decode_tokens_per_sec",
      "batch": args.batch, "prompt": args.prompt, "steps": args.steps,
      "per_config": results,
      "note": "batched greedy KV-cache decode; GQA shrinks the cache "
              "and its per-step HBM reads num_heads/num_kv_heads x; "
              "prefill_ms isolates the prompt pass (flash prefill vs "
              "the mha_dense_prefill pin)",
  }))


if __name__ == "__main__":
  main()
