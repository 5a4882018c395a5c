"""Runner ``serve_engine``: one ``ServingEngine`` on one chip under a closed
or an open loop.

A fresh ``spawn`` child (the one process on the chip) builds the engine
(default contiguous slab, default buckets and horizon, ``max_restarts=0``),
warms every shape the mix uses, ramps the load to its steady state, measures
``--seconds``, and only then, with the engine stopped and its memory freed,
runs the plain reference over a seeded sample of the requests the window
finished.  Started as a copy of ``chip_smoke.py``'s ``serve_child``.

Traffic file keys: ``loop`` (``closed``: ``clients`` requests always
outstanding; ``open``: Poisson arrivals at ``rate`` requests/s drawn from
``arrival_seed``, timed from when each was due), ``slots``, ``max_seq``, ``mix`` (see
``benchmarks/lib/traffic.size_pool``), ``ramp_seconds``, ``drain_seconds``,
``check_requests``, ``trace_seconds``, ``limits``.
"""

import gc
import json
import multiprocessing
import os
import sys
import time

from benchmarks.lib import compare
from benchmarks.lib import loader
from benchmarks.lib import traffic as traffic_lib

CHILD_TIMEOUT_S = 1100
RESULT_TIMEOUT_S = 600


def _ledger(req, due_at, late_s) -> dict:
  """One request's timing ledger as the engine wrote it (monotonic host
  clock), plus when the benchmark's schedule had it due."""
  return dict(
      due_at=due_at, late_s=late_s, submitted_at=req.submitted_at,
      started_at=req.started_at, prefill_done_at=req.prefill_done_at,
      first_token_at=req.first_token_at, finished_at=req.finished_at,
      prompt_len=int(len(req.prompt)), out_tokens=len(req.tokens),
      budget=req.max_new_tokens,
      error=None if req.error is None else type(req.error).__name__)


class _Load(object):
  """The load generator: one thread (the child's main thread), the
  requests it has outstanding, and the ledgers of those that ended."""

  def __init__(self, eng, stream):
    self.eng, self.stream = eng, stream
    self.live = {}              # rid -> (Request handle, due_at, late_s)
    self.ended = []             # (ledger, prompt, tokens) of ended requests
    self.rejected = 0

  def submit(self, due_at=None):
    prompt, out_len = next(self.stream)
    now = time.monotonic()
    try:
      rid = self.eng.submit(prompt, max_new_tokens=out_len)
    except Exception as e:     # noqa: BLE001 - a refusal is a failed request
      self.rejected += 1
      self.ended.append((dict(due_at=due_at or now, late_s=0.0,
                              submitted_at=now, first_token_at=None,
                              finished_at=now, started_at=None,
                              prefill_done_at=None, prompt_len=len(prompt),
                              out_tokens=0, budget=out_len,
                              error=type(e).__name__), None, None))
      return
    self.live[rid] = (self.eng.request(rid), due_at or now,
                      0.0 if due_at is None else now - due_at)

  def reap(self) -> int:
    """Move finished requests to ``ended``; returns how many."""
    import numpy as np
    done = [rid for rid, (req, _, _) in self.live.items()
            if req.done.is_set()]
    for rid in done:
      req, due_at, late_s = self.live.pop(rid)
      try:
        self.eng.poll(rid)          # pops the engine's registry entry
      except Exception:             # noqa: BLE001 - the ledger has the error
        pass
      self.ended.append((_ledger(req, due_at, late_s),
                         np.asarray(req.prompt),
                         np.asarray(req.tokens, np.int32)))
    return len(done)

  def delivered(self) -> int:
    """Output tokens delivered so far to requests still outstanding."""
    return sum(len(req.tokens) for req, _, _ in self.live.values())


def _closed_loop(load, eng, tr, seconds, tracer, counter):
  """``clients`` requests outstanding at all times: a completion submits
  the next.  Returns (w0, w1, tokens delivered inside the window, the
  engine's counters over the window)."""
  for _ in range(tr["clients"]):
    load.submit()
  t_ramp = time.monotonic()
  while time.monotonic() - t_ramp < tr["ramp_seconds"]:
    for _ in range(load.reap()):
      load.submit()
    time.sleep(0.002)
  load.ended.clear()
  counter.mark()
  snap = eng.stats_snapshot()
  before = load.delivered()
  w0 = time.monotonic()
  while time.monotonic() - w0 < seconds:
    for _ in range(load.reap()):
      load.submit()
    time.sleep(0.002)
  load.reap()
  w1 = time.monotonic()
  after = load.delivered() + sum(
      led["out_tokens"] for led, _, _ in load.ended if not led["error"])
  delta, ended = snap.delta(), list(load.ended)
  # --trace 1: the same load a few seconds longer, under the profiler
  tracer.start()
  while not tracer.expired():
    for _ in range(load.reap()):
      load.submit()
    time.sleep(0.002)
  tracer.stop()
  load.ended = ended
  return w0, w1, after - before, delta


def _open_loop(load, eng, tr, seconds, seed, tracer, counter):
  """Poisson arrivals at ``rate``; a ramp at the same rate first, so the
  window opens on a system already in its steady state.  Only requests due
  inside the window are measured; they are waited for after it closes."""
  ramp = tr["ramp_seconds"]
  # the schedule is the traffic file's, the same for every --seed: a tail is
  # a property of one schedule, and another schedule is other work
  aseed = tr["arrival_seed"]
  due = [t - ramp for t in traffic_lib.poisson_arrivals(
      tr["rate"], ramp, aseed + 1)] + traffic_lib.poisson_arrivals(
          tr["rate"], seconds, aseed)
  if tracer.enabled:      # arrivals go on through the traced stretch
    due += [seconds + t for t in traffic_lib.poisson_arrivals(
        tr["rate"], tracer.duration, aseed + 2)]
  w0 = time.monotonic() + ramp
  w1_due = w0 + seconds
  snap, marked, delta, w1 = None, False, None, None

  def close_window():
    d = snap.delta()
    d["outstanding_at_close"] = len(load.live)
    tracer.start()
    return time.monotonic(), d

  for t in due:
    while True:
      now = time.monotonic() - w0
      if now >= 0 and not marked:
        load.reap()
        load.ended.clear()
        counter.mark()
        snap, marked = eng.stats_snapshot(), True
      if now >= seconds and delta is None:
        w1, delta = close_window()
      if now >= t:
        break
      time.sleep(min(0.002, t - now))
      load.reap()
    load.submit(due_at=w0 + t)
  while delta is None:
    if time.monotonic() - w0 >= seconds:
      w1, delta = close_window()
    time.sleep(0.002)
    load.reap()
  while not tracer.expired():
    time.sleep(0.002)
    load.reap()
  tracer.stop()
  deadline = w1 + tr["drain_seconds"]

  def measured(due_at):
    return w0 <= due_at < w1_due

  while any(measured(d) for _, d, _ in load.live.values()) \
      and time.monotonic() < deadline:
    time.sleep(0.005)
    load.reap()
  # only requests due inside the window are measured
  load.ended = [e for e in load.ended if measured(e[0]["due_at"])]
  unfinished = [(_ledger(req, due_at, late), None, None)
                for req, due_at, late in load.live.values()
                if measured(due_at)]
  for led, _, _ in unfinished:
    led["error"] = "UnfinishedAtDrain"
  load.ended.extend(unfinished)
  return w0, w1, None, delta


def _reference_gaps(family, config, seed, sample, max_seq, control: bool):
  """Run the plain reference once over each sampled request's prompt with
  its served tokens; for every served token, how far its reference logit
  lies below the reference's best.  With ``control``, also the gap of the
  token a lower-precision (fp8) reference puts first at each of the same
  positions."""
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

  @jax.jit
  def control_fn(w, toks, z):
    low = family.reference_logits(w, toks, config, "fp8")[0]
    first = jnp.argmax(low[:-1], axis=-1)
    picked = jnp.take_along_axis(z[:-1], first[:, None], axis=-1)[:, 0]
    return jnp.max(z[:-1], axis=-1) - picked

  out, ctl = [], []
  for prompt, tokens in sample:
    n, plen = len(prompt) + len(tokens), len(prompt)
    buf = np.zeros((1, max_seq), np.int32)      # causal: the tail is inert
    buf[0, :plen], buf[0, plen:n] = prompt, tokens
    g, z = gaps_fn(w, jnp.asarray(buf))
    out.append(np.asarray(g)[plen - 1:n - 1])
    if control:
      ctl.append(np.asarray(control_fn(w, jnp.asarray(buf), z))[plen - 1:n - 1])
    del z
  return out, ctl


def child_main(spec, report_path):
  import numpy as np
  import jax
  from tensorflowonspark_tpu import serving
  from tensorflowonspark_tpu.utils import compile_cache
  from benchmarks.lib import device as dev_lib

  compile_cache.setup()
  dev_lib.cache_every_program()
  counter = dev_lib.CompileCounter()
  dev = dev_lib.device_record(spec["chips"], spec["rehearse"])
  tr, config, seed = spec["traffic"], spec["config"], spec["seed"]
  family = loader.load_module("families", config["family"])
  mix = tr["mix"]

  t0 = time.monotonic()
  cfg = family.program_config(config, tr["max_seq"])
  params = family.program_params(seed, config, "bfloat16")
  jax.block_until_ready(params)
  eng = serving.ServingEngine(params, cfg, num_slots=tr["slots"],
                              max_restarts=0).start()
  if eng.page_size != 0:
    raise RuntimeError("the default cache layout is the contiguous slab")
  weights_s = time.monotonic() - t0

  # warm every shape the mix uses: one request of each prompt length (the
  # bucket programs, insert, step_many and the small per-length slices)
  t0 = time.monotonic()
  rng = np.random.default_rng([seed, 9])
  rids = [eng.submit(rng.integers(0, config["vocab_size"], n, dtype=np.int32),
                     max_new_tokens=2 * eng.horizon)
          for n in sorted(set(mix["prompt_lens"]))]
  for rid in rids:
    eng.result(rid, timeout=RESULT_TIMEOUT_S)
  warm_s = time.monotonic() - t0

  tracer = dev_lib.Tracer(spec["trace"], os.path.join(spec["run_dir"], "trace"),
                          tr["trace_seconds"])
  load = _Load(eng, traffic_lib.request_stream(mix, seed,
                                               config["vocab_size"]))
  t_ramp0 = time.time()
  if tr["loop"] == "closed":
    w0, w1, tokens, delta = _closed_loop(load, eng, tr, spec["seconds"],
                                         tracer, counter)
  else:
    w0, w1, tokens, delta = _open_loop(load, eng, tr, spec["seconds"], seed,
                                       tracer, counter)
  tracer.stop()
  in_window = counter.since_mark()
  t_window_start = time.time() - (time.monotonic() - w0)

  peak, mem_stats = dev_lib.memory_peak_bytes(), dev_lib.memory_stats()
  stats_all = {k: v for k, v in eng.stats.items()
               if isinstance(v, (int, float))}
  ended, horizon = load.ended, eng.horizon
  eng.stop()
  del eng, params, load
  gc.collect()
  summary = tracer.reduce()
  if summary is not None:
    from benchmarks.lib import trace as trace_lib
    summary = trace_lib.reduce_directory(
        os.path.join(spec["run_dir"], "trace"),
        default_gap_label="engine-loop")

  # the plain reference, after the window, the engine stopped and freed:
  # a seeded sample of the requests the window finished, the longest in it
  t0 = time.monotonic()
  ok = [i for i, (led, p, t) in enumerate(ended)
        if not led["error"] and t is not None and len(t)]
  sample_idx = []
  if ok:
    longest = max(ok, key=lambda i: ended[i][0]["prompt_len"]
                  + ended[i][0]["out_tokens"])
    rest = [i for i in ok if i != longest]
    pick = np.random.default_rng([seed, 11]).permutation(len(rest))
    sample_idx = [longest] + [rest[j] for j in
                              pick[:max(0, tr["check_requests"] - 1)]]
  gaps, ctl = _reference_gaps(
      family, config, seed, [(ended[i][1], ended[i][2]) for i in sample_idx],
      tr["max_seq"], bool(spec.get("control")))
  reference_s = time.monotonic() - t0
  all_gaps = np.concatenate(gaps) if gaps else np.zeros((0,), np.float32)
  ctl_gaps = np.concatenate(ctl) if ctl else None

  report = dict(
      device=dev, memory_peak_bytes=peak, memory_stats=mem_stats,
      loop=tr["loop"], window_s=w1 - w0, w0=w0, w1=w1,
      tokens_in_window=tokens, stats_delta=delta, stats_all=stats_all,
      requests=[led for led, _, _ in ended], rejected=0,
      slots=tr["slots"], horizon=horizon,
      checked_requests=len(sample_idx), checked_tokens=int(len(all_gaps)),
      served_gap_max=float(all_gaps.max()) if len(all_gaps) else None,
      served_gap_p99=float(np.percentile(all_gaps, 99)) if len(all_gaps)
      else None,
      served_gap_mean=float(all_gaps.mean()) if len(all_gaps) else None,
      control_gap_max=float(ctl_gaps.max()) if ctl else None,
      control_gap_mean=float(ctl_gaps.mean()) if ctl else None,
      control_gap_p99=float(np.percentile(ctl_gaps, 99)) if ctl else None,
      reference_s=reference_s, compile=counter.record(),
      compiles_in_window=in_window, weights_s=weights_s, warm_s=warm_s,
      ramp_s=tr["ramp_seconds"], t_window_start=t_window_start,
      t_ramp_start=t_ramp0, trace_summary=summary,
      cache_dir=compile_cache.cache_dir(), pid=os.getpid())
  with open(report_path + ".tmp", "w") as f:
    json.dump(report, f)
  os.replace(report_path + ".tmp", report_path)


# ---------------------------------------------------------------------------
# the parent: orchestration only, never JAX
# ---------------------------------------------------------------------------


def checks_from(rep: dict, limits: dict) -> list:
  d = rep["stats_all"]
  return [
      compare.check("engine_restarts", d["engine_restarts"], 0, "eq"),
      compare.check("replay_mismatches", d["replay_mismatches"], 0, "eq"),
      compare.check("checked_tokens", rep["checked_tokens"],
                    limits["checked_tokens_min"], "ge"),
      compare.check("served_logit_gap_max", rep["served_gap_max"],
                    limits["served_logit_gap_max"]),
  ]


def run(spec: dict) -> dict:
  tr = spec["traffic"]
  report_path = os.path.join(spec["run_dir"], "serve.json")
  ctx = multiprocessing.get_context("spawn")
  proc = ctx.Process(target=child_main, args=(spec, report_path),
                     name="bench-serve")
  proc.start()
  proc.join(CHILD_TIMEOUT_S)
  if proc.is_alive():
    proc.kill()
    proc.join(10)
    raise RuntimeError("serve child exceeded %d s" % CHILD_TIMEOUT_S)
  if proc.exitcode != 0:
    raise RuntimeError("serve child exited %r" % proc.exitcode)
  if "jax" in sys.modules and not spec["rehearse"]:
    raise RuntimeError("the parent touched JAX")
  rep = loader.load_json(report_path)

  reqs = rep["requests"]
  failed = sum(1 for r in reqs if r["error"])
  rep["checks"] = checks_from(rep, tr["limits"])
  rep["attempted"], rep["failed"] = len(reqs), failed
  rep["setup_s"] = rep["t_window_start"] - spec["t_start"]
  from benchmarks.lib import stats
  e2e = dict(setup_s=rep["setup_s"])
  if tr["loop"] == "closed":
    e2e["serve_tok_s"] = rep["tokens_in_window"] / rep["window_s"]
  else:
    e2e["ttft_p95_ms"] = stats.percentile(
        [stats.ttft_ms(r) for r in reqs], 95)
    tp = [stats.tpot_ms(r) for r in reqs]
    e2e["tpot_p95_ms"] = stats.percentile([x for x in tp if x is not None], 95)
    e2e["latency_p95_ms"] = stats.percentile(
        [stats.latency_ms(r) for r in reqs], 95)
  rep["end_to_end"] = e2e
  comp, d = rep["compile"], rep["stats_delta"]
  rep["notes"] = [
      "%s loop, %d slots, window %.3f s: %d requests ended (%d failed), "
      "steps %d, prefills %d, emitted %d, rejected %d, expired %d"
      % (tr["loop"], tr["slots"], rep["window_s"], len(reqs), failed,
         d["steps"], d["prefills"], d["emitted_tokens"], d["rejected"],
         d["expired"]),
      "compilations inside the window %d (expected 0); compiles %d in "
      "%.1f s, cache hits %d misses %d; weights+engine %.1f s, warm-up "
      "%.1f s, ramp %.1f s"
      % (rep["compiles_in_window"], comp["compiles"], comp["compile_s"],
         comp["cache_hits"], comp["cache_misses"], rep["weights_s"],
         rep["warm_s"], rep["ramp_s"]),
      "reference %.1f s over %d requests, %d served tokens (after the "
      "window, not in setup_s): gap max %r p99 %r mean %r"
      % (rep["reference_s"], rep["checked_requests"], rep["checked_tokens"],
         rep["served_gap_max"], rep["served_gap_p99"],
         rep["served_gap_mean"]),
      "memory_stats %s" % json.dumps(rep["memory_stats"], sort_keys=True),
  ]
  if tr["loop"] == "open":
    mid = (rep["w0"] + rep["w1"]) / 2

    def qwait(rs):
      xs = [(r["started_at"] - r["submitted_at"]) * 1e3 for r in rs
            if r.get("started_at")]
      return stats.percentile(xs, 50) if xs else None
    rep["notes"].append(
        "rate %r/s: ttft p50 %r p95 %r ms, tpot p50 %r p95 %r ms; queue "
        "wait p50 first half %r, second half %r ms; outstanding at close %d"
        % (tr["rate"], stats.percentile([stats.ttft_ms(r) for r in reqs], 50),
           e2e["ttft_p95_ms"],
           stats.percentile([x for x in tp if x is not None], 50),
           e2e["tpot_p95_ms"],
           qwait([r for r in reqs if r["due_at"] < mid]),
           qwait([r for r in reqs if r["due_at"] >= mid]),
           d["outstanding_at_close"]))
  if rep["control_gap_max"] is not None:
    rep["notes"].append(
        "CONTROL (fp8 reference's first token, gap under the f32 reference): "
        "max %r p99 %r mean %r" % (rep["control_gap_max"],
                                   rep["control_gap_p99"],
                                   rep["control_gap_mean"]))
  return rep
