"""The serving loop's phases through the one span seam
(``obs.spans.region``): the always-on counters in ``ServingEngine.stats``,
the recorder spans under ``TOS_OBS=1`` and the ``jax.profiler`` trace
annotations — CPU, toy engine.
"""

import contextlib
import dataclasses
import glob
import itertools
import os
import subprocess
import sys
import threading
import time
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.obs import spans as spans_mod
from tensorflowonspark_tpu.serving import ServingEngine

PHASE_KEYS = ("t_reap_s", "t_idle_s", "t_admit_s", "t_prefill_s",
              "t_prefill_sync_s", "t_insert_s", "t_decode_prep_s",
              "t_decode_dispatch_s", "t_decode_fetch_s",
              "t_decode_harvest_s")
COUNT_KEYS = ("decode_dispatches", "prefill_chunks")
EMPTY_KEYS = tuple(spans_mod.empty_key(k) for k in PHASE_KEYS)


@pytest.fixture(scope="module")
def toy():
  # wide enough that a dispatch outweighs the Python between two regions
  # (a prompt is ONE chunk since the padded prefill plan, so a run is
  # mostly decode passes, each a few lines of Python outside any counter)
  cfg = tfm.TransformerConfig(vocab_size=64, num_layers=4, num_heads=2,
                              d_model=256, d_ff=1024, max_seq_len=96,
                              remat=False, dtype=jnp.float32)
  return cfg, tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=16)


def _prompts(n, seed=3, longest=60):
  rng = np.random.RandomState(seed)
  return [rng.randint(1, 64, (k,)).astype(np.int32)
          for k in rng.randint(2, longest, n)]


def _serve(eng, prompts, budget):
  rids = [eng.submit(p, max_new_tokens=budget) for p in prompts]
  for rid in rids:
    eng.result(rid, timeout=120)


# -- the seam itself ----------------------------------------------------------


def test_region_self_time_partitions_nested_regions(monkeypatch):
  """A counter gets SELF time: duration less what nested regions put into
  their own counters; a nested region without a counter stays in its
  parent's.  A scripted clock makes the arithmetic exact."""
  ticks = iter([0.0,            # outer in
                1.0, 3.0,       # a in/out (2 s, counted)
                4.0,            # b in (no counter)
                5.0, 5.5,       # c in/out (0.5 s, counted, inside b)
                7.0,            # b out
                10.0])          # outer out
  monkeypatch.setattr(spans_mod.time, "monotonic", lambda: next(ticks))
  acc = dict(outer=0.0, a=0.0, c=0.0)
  with spans_mod.region("outer", acc, "outer", record=False) as outer:
    with spans_mod.region("a", acc, "a", record=False):
      pass
    with spans_mod.region("b", record=False) as b:
      with spans_mod.region("c", acc, "c", record=False):
        pass
  assert (outer.dur, b.dur) == (10.0, 3.0)
  assert acc == dict(outer=7.5, a=2.0, c=0.5)
  assert sum(acc.values()) == outer.dur


def _scripted_clock(monkeypatch, ticks):
  ticks = iter(ticks)
  monkeypatch.setattr(spans_mod.time, "monotonic", lambda: next(ticks))


def test_device_queue_counts_from_a_read_to_the_next_dispatch(monkeypatch):
  """Known drained: from the return of a read of the NEWEST dispatch to the
  return of the next dispatch call, and never before the first read."""
  _scripted_clock(monkeypatch, [10.0,       # drained(1)
                                12.5,       # dispatched() -> 2
                                20.0,       # drained(2)
                                21.0])      # unknown()
  q = spans_mod.DeviceQueue()
  assert not q.known_drained and q.empty_at(5.0) == 0.0
  assert q.dispatched() == 1 and not q.known_drained   # nothing was open
  assert q.empty_at(9.0) == 0.0
  q.drained(1)
  assert q.known_drained and q.empty_at(11.0) == 1.0
  assert q.dispatched() == 2 and q.seq == 2
  assert not q.known_drained and q.empty_at(15.0) == 2.5
  q.drained(2)
  q.drained(2)                        # a second read of it: no clock, no-op
  assert q.empty_at(20.5) == 3.0
  q.unknown()
  assert not q.known_drained and q.empty_at(30.0) == 3.5
  q.unknown()                         # idempotent, reads no clock


def test_device_queue_ignores_the_read_of_an_older_dispatch(monkeypatch):
  """Two programs in flight (what dispatching the next step before fetching
  the last one's tokens will do): the older one's read returning proves
  nothing, so nothing is counted until the newest has been read."""
  _scripted_clock(monkeypatch, [7.0])  # the one read that counts
  q = spans_mod.DeviceQueue()
  first, second = q.dispatched(), q.dispatched()
  q.drained(first)                    # out of order: a newer one in flight
  assert not q.known_drained and q.empty_at(100.0) == 0.0
  q.drained(second)
  assert q.known_drained and q.empty_at(9.0) == 2.0


def test_device_queue_forgets_across_a_crash(monkeypatch):
  """A crash between ``dispatched()`` and ``drained()``: the read never
  returned, the loop marks the queue unknown, and the dead program's number
  can no longer drain it."""
  _scripted_clock(monkeypatch, [])    # no edge here reads the clock
  q = spans_mod.DeviceQueue()
  seq = q.dispatched()
  q.unknown()                         # _recover
  later = q.dispatched()              # the rebuilt engine's first program
  q.drained(seq)                      # a stale number
  assert later == seq + 1 and not q.known_drained
  assert q.empty_at(1e9) == 0.0


def test_region_charges_drained_seconds_to_the_innermost_counter(monkeypatch):
  """``empty_*`` follows the rule of self time on the same clock readings:
  each drained second to the innermost open region that has a counter, a
  wait is never empty, what follows the read in its region is."""
  _scripted_clock(monkeypatch, [
      0.0,              # outer in
      1.0,              # dispatch in (not known drained: dispatched()
      3.0,              # reads no clock), dispatch out
      3.0,              # fetch in
      7.0,              # drained(): the wait took 4 s
      8.0,              # fetch out: 1 s of tail
      8.0,              # bare in (no counter)
      9.0, 9.5,         # inner in/out (counted, inside bare)
      10.0,             # bare out
      11.0,             # redispatch in (entered drained)
      12.0,             # dispatched(): empty to the call's return
      13.0,             # redispatch out
      14.0])            # outer out
  keys = ("t_outer_s", "t_dispatch_s", "t_fetch_s", "t_inner_s")
  acc = {k: 0.0 for k in keys + tuple(map(spans_mod.empty_key, keys))}
  q = spans_mod.DeviceQueue()

  def reg(name, key=None):
    return spans_mod.region(name, acc if key else None, key, record=False,
                            queue=q)

  with reg("outer", "t_outer_s"):
    with reg("dispatch", "t_dispatch_s"):
      seq = q.dispatched()
    with reg("fetch", "t_fetch_s"):
      q.drained(seq)
    with reg("bare"):
      with reg("inner", "t_inner_s"):
        pass
    with reg("redispatch", "t_dispatch_s"):
      q.dispatched()
  assert acc == dict(
      t_outer_s=14.0 - 2.0 - 5.0 - 0.5 - 2.0, t_dispatch_s=4.0,
      t_fetch_s=5.0, t_inner_s=0.5,
      # outer: bare's 2 s less inner's 0.5, and 10 -> 11 between regions
      empty_outer_s=2.5, empty_dispatch_s=1.0, empty_fetch_s=1.0,
      empty_inner_s=0.5)
  total = sum(acc[spans_mod.empty_key(k)] for k in keys)
  assert total == q.empty_at(99.0) == 5.0
  assert all(acc[spans_mod.empty_key(k)] <= acc[k] for k in keys)


def test_region_records_like_span_when_the_plane_is_on():
  rec = spans_mod.activate()
  try:
    with spans_mod.region("x.kept", trace="t1", n=np.int32(3)) as r:
      r.attrs["late"] = 7
    with spans_mod.region("x.dropped", record=False):
      pass
  finally:
    spans_mod.deactivate()
  (got,) = rec.drain()
  assert got["name"] == "x.kept" and got["trace"] == "t1"
  assert got["attrs"] == {"n": 3, "late": 7} and got["ph"] == "X"
  assert got["t0"] == r.t0 and got["dur"] == r.dur
  assert got["tid"] == threading.current_thread().name


def test_spans_and_control_plane_import_without_jax():
  """``obs/spans`` rides into processes that must stay off the chip
  (rendezvous, the benchmark's parent): importing it, and running a
  region there, never loads JAX."""
  code = (
      "import sys\n"
      "import tensorflowonspark_tpu.obs.spans as s\n"
      "import tensorflowonspark_tpu.control.rendezvous\n"
      "acc = {'k': 0.0}\n"
      "with s.region('a.b', acc, 'k'):\n"
      "  pass\n"
      "assert acc['k'] >= 0 and s._annotation('a.b') is s._NO_ANNOTATION\n"
      "assert 'jax' not in sys.modules, 'jax was loaded'\n")
  out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
  assert out.returncode == 0, out.stderr[-2000:]


# -- the counter sink ---------------------------------------------------------


def test_phase_keys_exist_at_construction(toy):
  cfg, state = toy
  eng = ServingEngine(state.params, cfg, num_slots=2)
  for k in PHASE_KEYS:
    assert eng.stats[k] == 0.0 and isinstance(eng.stats[k], float)
  for k in COUNT_KEYS:
    assert eng.stats[k] == 0 and isinstance(eng.stats[k], int)
  for k in EMPTY_KEYS:
    assert eng.stats[k] == 0.0 and isinstance(eng.stats[k], float)
  assert EMPTY_KEYS[5] == "empty_insert_s" and len(set(EMPTY_KEYS)) == 10
  # a reader that takes every t_* key for a phase still gets the ten
  assert sorted(k for k in eng.stats if k.startswith("t_")) \
      == sorted(PHASE_KEYS)
  # the benchmark's delta surface carries every one of them
  delta = eng.stats_snapshot().delta()
  assert set(PHASE_KEYS + COUNT_KEYS + EMPTY_KEYS) <= set(delta)
  assert all(delta[k] == 0.0 for k in EMPTY_KEYS)


def test_phase_counters_never_decrease_and_close_on_the_loops_wall_time(
    toy, monkeypatch):
  """Over a warm run of a few dozen requests, on the real clock: no counter
  decreases and no second is counted twice (the counters sum to no more than
  the loop thread's lifetime). Then the same run on a clock the test owns,
  one tick a reading, so nothing depends on how the host shares its cores:
  the counters sum EXACTLY to the ticks inside the loop's outermost counted
  regions, and every reading taken outside those is a region's own edge:
  nothing the loop times (a phase, an edge of the device queue) is outside
  a counter."""
  cfg, state = toy
  eng = ServingEngine(state.params, cfg, num_slots=4, eos_id=None)
  lives, inner = [], eng._loop

  def timed_loop():
    t0 = spans_mod.time.monotonic()
    inner()
    lives.append((t0, spans_mod.time.monotonic(), threading.get_ident()))

  eng._loop = timed_loop
  prompts = _prompts(48)
  with eng:                             # cold: every shape compiles
    _serve(eng, prompts[:12], 12)
  base = dict(eng.stats)
  seen, stop = [], threading.Event()

  def watch():
    while not stop.is_set():
      seen.append([eng.stats[k] for k in PHASE_KEYS + COUNT_KEYS])
      time.sleep(0.002)

  watcher = threading.Thread(target=watch, daemon=True)
  with eng:                             # warm: a fresh loop thread
    watcher.start()
    _serve(eng, prompts, 12)
  stop.set()
  watcher.join(10)
  assert not watcher.is_alive() and len(seen) > 5 and len(lives) == 2
  for a, b in zip(seen, seen[1:]):
    assert all(y >= x for x, y in zip(a, b))
  delta = {k: eng.stats[k] - base[k] for k in PHASE_KEYS}
  assert all(v >= 0 for v in delta.values())
  assert delta["t_decode_dispatch_s"] > 0 and delta["t_prefill_s"] > 0
  assert sum(delta.values()) <= lives[1][1] - lives[1][0]
  # under the pass's order: admissions went behind a running decode step
  assert eng.stats["admits_ahead"] - base["admits_ahead"] > 0

  # the test's own clock: whole numbers, so every sum below is exact
  ticks = itertools.count()
  monkeypatch.setattr(spans_mod, "time", types.SimpleNamespace(
      monotonic=lambda: float(next(ticks))))
  # counted regions open, on the ONE thread that runs regions (asserted)
  regions, depth, real_region = [], [0], spans_mod.region

  @contextlib.contextmanager
  def logged_region(name, acc=None, key=None, **kw):
    counted = acc is not None
    outermost = counted and not depth[0]
    depth[0] += counted
    try:
      with real_region(name, acc, key, **kw) as r:
        yield r
    finally:
      depth[0] -= counted
      regions.append((threading.get_ident(), r.t0, r.t0 + r.dur, outermost))

  monkeypatch.setattr(spans_mod, "region", logged_region)
  base = dict(eng.stats)
  with eng:
    _serve(eng, prompts, 12)
  monkeypatch.undo()
  t_in, t_out, loop_thread = lives[2]
  assert all(who == loop_thread for who, _, _, _ in regions)
  spans = [(a, b) for _, a, b, outermost in regions if outermost]
  total = sum(eng.stats[k] - base[k] for k in PHASE_KEYS)
  assert total == sum(b - a for a, b in spans) and total > 0
  accounted = {t_in, t_out}
  for _, a, b, outermost in regions:
    accounted.update((a, b))
    if outermost:
      accounted.update(range(int(a), int(b)))
  assert [t for t in range(int(t_in), int(t_out)) if t not in accounted] == []


@pytest.mark.parametrize("stack", ["plain", "spec", "paged"])
def test_empty_counters_hold_their_invariants_under_a_watcher(toy, stack):
  """The four invariants of the ``empty_*_s`` counters, sampled by a watcher
  thread while the loop serves (``dict(stats)`` is one atomic copy): never
  over the phase's ``t_*_s``, never decreasing; their sum is the queue's own
  total, no second twice and none outside a region; a wait is never empty;
  and an idle engine's ``empty_idle_s`` follows ``t_idle_s``."""
  cfg, state = toy
  kw = dict(plain={}, spec=dict(spec_depth=2, spec_layers=1),
            paged=dict(page_size=8))[stack]
  eng = ServingEngine(state.params, cfg, num_slots=4, eos_id=None, **kw)
  known_at_exit, inner = [], eng._loop

  def loop():
    inner()
    known_at_exit.append(eng._devq.empty_at(time.monotonic()))

  eng._loop = loop
  prompts = _prompts(24, seed=9)
  with eng:                             # cold: every shape compiles
    _serve(eng, prompts[:8], 10)
  seen, stop = [], threading.Event()

  def watch():
    while not stop.is_set():
      seen.append(dict(eng.stats))
      time.sleep(0.002)

  watcher = threading.Thread(target=watch, daemon=True)
  # both readings with the loop stopped: inside the `with`, an idle region
  # already open would charge its seconds from before `known0` at its exit
  base = dict(eng.stats)
  known0 = eng._devq.empty_at(time.monotonic())
  with eng:                             # warm: a fresh loop thread
    watcher.start()
    _serve(eng, prompts, 10)
    # the engine empties: the first idle pass may still find a freed lane's
    # reset_slots running, every later one vouches for the whole wait
    time.sleep(0.1)
    idle0 = dict(eng.stats)
    time.sleep(0.5)
    idle1 = dict(eng.stats)
  stop.set()
  watcher.join(10)
  assert not watcher.is_alive() and len(seen) > 5
  for st in seen:
    for t_key, e_key in zip(PHASE_KEYS, EMPTY_KEYS):
      assert 0.0 <= st[e_key] <= st[t_key] + 1e-9, (t_key, st)
  for a, b in zip(seen, seen[1:]):
    assert all(b[k] >= a[k] for k in EMPTY_KEYS)
  st = eng.stats                        # the loop has stopped
  delta = {k: st[k] - base[k] for k in PHASE_KEYS + EMPTY_KEYS}
  # every known-drained second of the run is in one counter, but for the
  # few lines of a loop pass that no region covers (the 2% of the closure
  # test above)
  known = known_at_exit[1] - known0
  empty = sum(delta[k] for k in EMPTY_KEYS)
  assert empty <= known + 1e-9
  assert empty == pytest.approx(known, rel=0.02)
  # something was counted where the loop dispatches into a drained device
  assert delta["empty_prefill_s"] > 0 and delta["empty_decode_harvest_s"] > 0
  # under the pass's order: the contiguous slab took lanes ahead of a
  # running step, the paged pool queued chunks behind one or none
  assert (st["admits_ahead"] - base["admits_ahead"] > 0) == (stack != "paged")
  # the waits: all but the tail after the read returned is NOT empty
  for key in ("decode_fetch", "prefill_sync"):
    assert delta["empty_%s_s" % key] < 0.5 * delta["t_%s_s" % key], key
  # idle: the device is drained for as long as the engine waits
  d_idle = idle1["t_idle_s"] - idle0["t_idle_s"]
  assert d_idle > 0.3
  # (a copy may fall between a pass's two additions: one poll of slack)
  assert idle1["empty_idle_s"] - idle0["empty_idle_s"] \
      == pytest.approx(d_idle, abs=eng._poll + 1e-3)


def test_a_crash_leaves_the_queue_unknown_until_the_next_read(
    toy, monkeypatch):
  """``_recover`` marks the queue not known drained, so the backoff, the
  slab's rebuild and the replayed prefill's dispatches count nothing until
  a read of the newest program returns; the engine then counts again."""
  from tensorflowonspark_tpu.utils import chaos
  cfg, state = toy
  monkeypatch.setenv("TOS_CHAOS_SERVE", "decode#2:raise")
  chaos.reset()
  marks = []
  try:
    eng = ServingEngine(state.params, cfg, num_slots=2, eos_id=None,
                        restart_backoff=0.2)
    recover = eng._recover

    def recording(error):
      ok = recover(error)
      # on return: the backoff has passed, nothing is vouched for
      marks.append((eng._devq.known_drained,
                    sum(eng.stats[k] for k in EMPTY_KEYS)))
      return ok

    eng._recover = recording
    with eng:
      _serve(eng, _prompts(3, seed=13, longest=20), 8)
      st = dict(eng.stats)
  finally:
    monkeypatch.delenv("TOS_CHAOS_SERVE")
    chaos.reset()
  assert st["engine_restarts"] == 1 and len(marks) == 1
  known, counted = marks[0]
  assert not known
  # the 0.2 s of backoff are in no counter, and the engine counted on
  assert sum(st[k] for k in EMPTY_KEYS) > counted
  for t_key, e_key in zip(PHASE_KEYS, EMPTY_KEYS):
    assert 0.0 <= st[e_key] <= st[t_key] + 1e-9


@pytest.mark.parametrize("spec_depth", [0, 2])
def test_dispatch_counters_are_exact(toy, spec_depth):
  cfg, state = toy
  prompts = _prompts(30, seed=5)
  with ServingEngine(state.params, cfg, num_slots=3, eos_id=None,
                     spec_depth=spec_depth, spec_layers=1) as eng:
    _serve(eng, prompts, 9)
  st = eng.stats               # read with the loop stopped
  per_dispatch = eng.horizon if spec_depth == 0 \
      else eng._spec_rounds * spec_depth
  assert st["prefills"] == len(prompts)
  plans = [eng.decoder.plan(len(p), 0, eng.buckets) for p in prompts]
  assert st["prefill_chunks"] == sum(len(plan) for plan in plans)
  assert st["prefill_chunks"] == len(prompts)    # each fits one bucket
  assert st["prefill_tokens"] == sum(seg for plan in plans for seg, _ in plan)
  assert st["prefill_tokens"] - st["prefill_padded_tokens"] \
      == sum(len(p) for p in prompts)
  assert st["decode_dispatches"] > 0
  assert st["decode_dispatches"] * per_dispatch == st["steps"]


@pytest.mark.parametrize("stack", ["loop", "dma", "paged"])
def test_cursor_leaf_writes_advance_by_leaves_times_horizon_a_dispatch(
    toy, monkeypatch, stack):
  """``cursor_leaf_writes`` counts the per-slot cursor writes of cache
  leaves the fused decode dispatches made (K and V of every layer, once a
  step of the horizon) and ``cursor_leaf_writes_dma`` those of them by
  ``ops.cursor_write``'s kernel: none on the CPU, all of them where Pallas
  kernels are on (interpret mode here; the toy's leaves are lane-dense, 2
  heads x 128). The paged pool writes otherwise and counts nothing."""
  from tensorflowonspark_tpu import ops
  cfg, state = toy
  cfg = dataclasses.replace(cfg, layer_norm_impl="flax",
                            attention_impl="dense")
  monkeypatch.setattr(ops, "pallas_kernels_enabled", lambda: stack == "dma")
  with ServingEngine(state.params, cfg, num_slots=3, eos_id=None,
                     page_size=8 if stack == "paged" else 0) as eng:
    _serve(eng, _prompts(6, seed=7, longest=20), 9)
  st = eng.stats               # read with the loop stopped
  assert st["decode_dispatches"] > 0
  a_dispatch = 0 if stack == "paged" else 2 * cfg.num_layers * eng.horizon
  assert st["cursor_leaf_writes"] == st["decode_dispatches"] * a_dispatch
  assert st["cursor_leaf_writes_dma"] == \
      (st["cursor_leaf_writes"] if stack == "dma" else 0)


@pytest.mark.parametrize("stack", ["dense", "ragged", "f32", "paged"])
def test_decode_attn_reads_advance_by_layers_times_horizon_a_dispatch(
    toy, monkeypatch, stack):
  """``decode_attn_reads`` counts the per-slot single-token cache reads the
  fused decode dispatches made (a layer's attention over its K and V
  leaves, once a step of the horizon) and ``decode_attn_reads_ragged`` those
  of them by ``ops.decode_attention``'s kernel, which stops at each slot's
  cursor: none on the CPU, all of them where Pallas kernels are on and the
  leaves are bf16 of whole blocks (interpret mode here), none again for
  float32 leaves. The paged pool reads otherwise and counts nothing."""
  from tensorflowonspark_tpu import ops
  cfg, state = toy
  cfg = dataclasses.replace(
      cfg, layer_norm_impl="flax", attention_impl="dense", max_seq_len=128,
      dtype=jnp.float32 if stack == "f32" else jnp.bfloat16)
  monkeypatch.setattr(ops, "pallas_kernels_enabled",
                      lambda: stack in ("ragged", "f32"))
  with ServingEngine(state.params, cfg, num_slots=3, eos_id=None,
                     page_size=8 if stack == "paged" else 0) as eng:
    _serve(eng, _prompts(6, seed=7, longest=20), 9)
  st = eng.stats               # read with the loop stopped
  assert st["decode_dispatches"] > 0
  a_dispatch = 0 if stack == "paged" else cfg.num_layers * eng.horizon
  assert st["decode_attn_reads"] == st["decode_dispatches"] * a_dispatch
  assert st["decode_attn_reads_ragged"] == \
      (st["decode_attn_reads"] if stack == "ragged" else 0)


# -- the recorder sink --------------------------------------------------------


@pytest.mark.parametrize("detail", ["1", "0"])
def test_recorder_names_and_gating(toy, monkeypatch, detail):
  """``TOS_OBS=1``: today's names keep coming, the per-dispatch phases
  join them under ``TOS_OBS_TRACE_DETAIL``, and the phases an IDLE engine
  runs every poll never reach the bounded recorder."""
  cfg, state = toy
  monkeypatch.setenv("TOS_OBS_TRACE_DETAIL", detail)
  rec = spans_mod.activate()
  try:
    with ServingEngine(state.params, cfg, num_slots=2, eos_id=None) as eng:
      rid = eng.submit(_prompts(1, longest=20)[0], max_new_tokens=6)
      list(eng.stream(rid, timeout=120))
  finally:
    spans_mod.deactivate()
  stats = eng.stats            # read with the loop stopped: every region shut
  recs = rec.drain()
  names = {r["name"] for r in recs}
  assert {"serve.queue", "serve.prefill", "serve.decode",
          "serve.stream"} <= names
  fine = {"serve.prefill.chunk", "serve.prefill.sync", "serve.insert",
          "serve.decode.slot", "serve.decode.prep", "serve.decode.dispatch",
          "serve.decode.fetch", "serve.decode.harvest"}
  assert fine <= names if detail == "1" else not fine & names
  assert not {"serve.reap", "serve.idle", "serve.admit"} & names
  decode = [r for r in recs if r["name"] == "serve.decode"]
  assert len(decode) == stats["decode_dispatches"]
  assert all(set(r["attrs"]) == {"horizon", "active"} for r in decode)
  if detail == "1":
    # a decode's phases lie inside it, on the region's own clock. The
    # first step of a run is left in flight unread (prep and dispatch
    # only); the pass that dispatches the second reads and harvests it
    d = decode[1]
    inside = [r for r in recs if r["name"].startswith("serve.decode.")
              and d["t0"] <= r["t0"] and r["t0"] + r["dur"]
              <= d["t0"] + d["dur"] + 1e-9]
    assert {"serve.decode.prep", "serve.decode.dispatch",
            "serve.decode.fetch", "serve.decode.harvest",
            "serve.decode.slot"} <= {r["name"] for r in inside}
    chunks = [r for r in recs if r["name"] == "serve.prefill.chunk"]
    assert len(chunks) == stats["prefill_chunks"]
    assert all(r.get("trace") for r in chunks)


# -- the trace-clock sink -----------------------------------------------------


def test_profiler_session_alone_shows_the_nested_phases(toy, tmp_path):
  """No ``TOS_OBS``: a ``jax.profiler`` session by itself puts the loop's
  phases on the host plane of the ``.xplane.pb``, nested as written."""
  from jax.profiler import ProfileData
  cfg, state = toy
  assert spans_mod.active() is None
  prompts = _prompts(4, seed=11, longest=30)
  with ServingEngine(state.params, cfg, num_slots=2, eos_id=None) as eng:
    _serve(eng, prompts[:1], 4)
    jax.profiler.start_trace(str(tmp_path))
    try:
      _serve(eng, prompts, 6)
      # the last result is delivered from INSIDE a harvest: let the loop
      # close its open regions (one whole idle wait) before the session ends
      idle, deadline = eng.stats["t_idle_s"], time.monotonic() + 30
      while eng.stats["t_idle_s"] == idle and time.monotonic() < deadline:
        time.sleep(0.01)
    finally:
      jax.profiler.stop_trace()
  (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
  lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events if e.name.startswith("serve.")]
           for plane in ProfileData.from_file(path).planes
           if plane.name.startswith("/host:") for line in plane.lines]
  # one thread: THIS engine's loop. An engine that an earlier test file of
  # the same worker process left polling shows too, but only ever idle
  (loop,) = [evs for evs in lines
             if any(n == "serve.decode" for n, _, _ in evs)]

  def inside(child, parent):
    outer = [(s, e) for n, s, e in loop if n == parent]
    inner = [(s, e) for n, s, e in loop if n == child]
    assert inner and outer, (child, parent)
    return all(any(s0 <= s and e <= e0 for s0, e0 in outer)
               for s, e in inner)

  for child, parent in (("serve.decode.harvest", "serve.decode"),
                        ("serve.decode.dispatch", "serve.decode"),
                        ("serve.prefill.chunk", "serve.prefill"),
                        # an admission queued behind a decode step reads
                        # its first token after the harvest, outside its
                        # serve.prefill but inside the pass's serve.admit
                        ("serve.prefill.sync", "serve.admit"),
                        ("serve.prefill", "serve.admit"),
                        ("serve.insert", "serve.admit")):
    assert inside(child, parent), (child, parent)
  assert {n for n, _, _ in loop} >= {"serve.reap", "serve.decode.prep",
                                     "serve.decode.fetch"}
