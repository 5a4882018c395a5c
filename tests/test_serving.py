"""Continuous-batching serving runtime tests (CPU, tiny real models).

The load-bearing claim is BIT-PARITY: whatever the scheduler does —
mixed lengths, EOS early-exit, slot reuse, bucketed chunked prefill,
decode horizons — every request's tokens must equal its own
single-request ``greedy_generate_kv`` decode. Everything else (slot
accounting, queue semantics, knobs) is bookkeeping around that.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.serving import (
    DEFAULT_BUCKETS, DeadlineExceeded, PagePool, PoisonedRequest,
    PrefixCache, Request, RequestCancelled, RequestQueue, ServingEngine,
    ServingOverloaded, SlotDecoder, chunk_plan)
from tensorflowonspark_tpu.utils import chaos

EOS = 7
PAD = 0


def _tiny(max_seq_len=48, **kw):
  return tfm.TransformerConfig(vocab_size=64, num_layers=2, num_heads=2,
                               d_model=32, d_ff=64,
                               max_seq_len=max_seq_len, remat=False,
                               dtype=jnp.float32, **kw)


@pytest.fixture(scope="module")
def tiny_state():
  cfg = _tiny()
  return cfg, tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=16)


def _reference(params, cfg, prompt, budget, eos_id=EOS):
  """Single-request decode truncated at its stop — the parity oracle."""
  out = np.asarray(tfm.greedy_generate_kv(
      params, cfg, jnp.asarray(prompt)[None], budget, eos_id=eos_id,
      pad_id=PAD))[0]
  gen = out[len(prompt):]
  stops = np.where(gen == eos_id)[0]
  stop = (int(stops[0]) + 1) if len(stops) else budget
  return np.concatenate([prompt, gen[:stop]])


class TestChunkPlan:
  def test_decomposition_properties(self):
    buckets = (128, 32, 8, 4, 2, 1)
    for plen in (1, 2, 5, 8, 37, 127, 128, 200):
      plan = chunk_plan(plen, buckets)
      assert sum(plan) == plen
      assert plan == sorted(plan, reverse=True)
      assert set(plan) <= set(buckets)
    assert chunk_plan(37, buckets) == [32, 4, 1]

  def test_missing_unit_bucket_is_appended(self):
    assert chunk_plan(5, (4,)) == [4, 1]

  def test_invalid_length_raises(self):
    with pytest.raises(ValueError, match="prompt length"):
      chunk_plan(0)


class TestRequestQueue:
  def test_fifo_and_bounded_wait(self):
    q = RequestQueue()
    assert q.pop_nowait() is None
    assert q.wait_nonempty(timeout=0.05) is False
    a, b = Request([1], 4), Request([2], 4)
    q.push(a)
    q.push(b)
    assert len(q) == 2
    assert q.token_mass == a.token_cost + b.token_cost
    assert q.wait_nonempty(timeout=0.05) is True
    assert q.pop_nowait() is a
    assert q.close(RuntimeError("bye")) == [b]
    assert len(q) == 0 and q.token_mass == 0

  def test_bounds_and_oversized_when_empty(self):
    q = RequestQueue()
    big = Request([1] * 10, 100)            # token_cost 110
    q.push_bounded(big, max_requests=2, max_tokens=50)  # empty: admitted
    with pytest.raises(ServingOverloaded) as ei:
      q.push_bounded(Request([1], 4), max_requests=2, max_tokens=50)
    assert ei.value.queue_depth == 1
    assert ei.value.queued_tokens == big.token_cost
    q.pop_nowait()
    q.push_bounded(Request([1], 4), max_requests=1, max_tokens=0)
    with pytest.raises(ServingOverloaded, match="TOS_SERVE_MAX_QUEUE"):
      q.push_bounded(Request([2], 4), max_requests=1, max_tokens=0)

  def test_closed_queue_refuses_push_atomically(self):
    """The submit-vs-loop-death race fix: close-and-drain happens under
    the same lock push uses, so a racing push lands before the drain or
    fails — never between (an orphan nobody would ever finish)."""
    from tensorflowonspark_tpu.serving.scheduler import QueueClosed
    q = RequestQueue()
    root = RuntimeError("loop died")
    assert q.close(root) == []
    with pytest.raises(QueueClosed) as ei:
      q.push(Request([1], 4))
    assert ei.value.__cause__ is root
    with pytest.raises(QueueClosed):
      q.push_bounded(Request([1], 4))
    # a second close keeps the FIRST verdict
    q.close(RuntimeError("later"))
    with pytest.raises(QueueClosed) as ei:
      q.push_front(Request([1], 4))
    assert ei.value.__cause__ is root
    q.reopen()
    q.push(Request([1], 4))
    assert len(q) == 1

  def test_reap_removes_matching_and_keeps_order(self):
    q = RequestQueue()
    reqs = [Request([i], 4) for i in range(1, 5)]
    for r in reqs:
      q.push(r)
    removed = q.reap(lambda r: r.rid in (reqs[1].rid, reqs[3].rid))
    assert removed == [reqs[1], reqs[3]]
    assert q.pop_nowait() is reqs[0]
    assert q.pop_nowait() is reqs[2]
    assert q.token_mass == 0

  def test_replay_suppression_dedups_and_checks_parity(self):
    r = Request([9, 9], 8)
    for t in (3, 4, 5):
      r.emit(t)
    r.begin_replay()
    assert r.generated == 0                 # budget math restarts
    assert r.emit(3) and r.emit(4)
    assert r.generated == 2
    assert r.emit(6) is False               # divergence is reported
    assert r.emit(7)                        # suppression exhausted: live
    assert r.tokens == [3, 4, 5, 7]
    # the stream saw each position once: 3,4,5 pre-crash, then 7
    seen = []
    while not r.stream_q.empty():
      seen.append(r.stream_q.get_nowait())
    assert seen == [3, 4, 5, 7]

  def test_finish_is_idempotent(self):
    r = Request([1], 2)
    first = RuntimeError("first verdict")
    r.finish(first)
    r.finish(RuntimeError("second"))
    assert r.error is first
    assert r.stream_q.get_nowait() is None
    assert r.stream_q.empty()               # exactly one sentinel


class TestSlotDecoder:
  def test_chunked_prefill_matches_single_shot(self, tiny_state):
    """The warm-cache (idx > 0) chunked-prefill path: a prompt prefilled
    in bucket chunks must leave the same cache numerics (to float
    tolerance — XLA fuses differently per chunk shape) and the IDENTICAL
    first token + decode stream as one whole-prompt prefill (the
    engine's correctness keystone)."""
    cfg, state = tiny_state
    prompt = np.random.RandomState(1).randint(1, 64, (14,)).astype(np.int32)
    dec = SlotDecoder(cfg, 1)

    def decode_from(cache, first, n=6):
      slabs = dec.insert(dec.init_slabs(), cache, 0)
      toks, tok = [first], first
      for _ in range(n):
        slabs, nxt = dec.step(state.params, slabs, [tok], [True])
        tok = int(np.asarray(nxt)[0])
        toks.append(tok)
      return toks

    whole_cache, whole_first = dec.prefill(state.params, prompt,
                                           buckets=(64,))
    whole_stream = decode_from(whole_cache, whole_first)

    def written(leaf):
      # what the cursor covers: a padded tail's entries past it are
      # garbage by design (masked, then overwritten by decode)
      leaf = np.asarray(leaf, np.float32)
      return leaf[:, :len(prompt)] if leaf.ndim > 1 else leaf

    for buckets in ((8, 4, 2, 1), (4, 1), (1,)):
      cache, first = dec.prefill(state.params, prompt, buckets=buckets)
      for a, b in zip(jax.tree.leaves(cache),
                      jax.tree.leaves(whole_cache)):
        np.testing.assert_allclose(written(a), written(b),
                                   atol=1e-5, rtol=1e-5)
      assert decode_from(cache, first) == whole_stream, buckets

  def test_step_advances_only_active_slots(self, tiny_state):
    cfg, state = tiny_state
    dec = SlotDecoder(cfg, 2)
    slabs = dec.init_slabs()
    row, first = dec.prefill(state.params, np.asarray([3, 4, 5], np.int32))
    slabs = dec.insert(slabs, row, 0)

    def cursors(s):
      from jax.tree_util import tree_flatten_with_path
      return [np.asarray(leaf) for path, leaf in
              tree_flatten_with_path(s)[0]
              if getattr(path[-1], "key", None) == "index"]

    before = cursors(slabs)
    assert all((c == [3, 0]).all() for c in before)
    slabs, nxt = dec.step(state.params, slabs, [first, PAD],
                          [True, False])
    after = cursors(slabs)
    assert all((c == [4, 0]).all() for c in after), \
        "live slot must advance, idle slot must stay frozen"
    assert int(np.asarray(nxt)[1]) == PAD

  @pytest.mark.parametrize("op", ["insert", "step", "step_many", "step_spec",
                                  "insert_pages", "reset_slots"])
  def test_slab_programs_run_in_place(self, tiny_state, op):
    """Every program that returns a slab takes its slab DONATED and uses
    the donation: the slab that went in is deleted by the call, and JAX
    raised no "donated buffers were not usable" warning (how a layout or
    shape mismatch between the slab going in and coming out would show:
    the program would then copy the whole slab in silence). The row cache
    is not donated."""
    import warnings
    from tensorflowonspark_tpu.serving import slots as slots_lib
    cfg, state = tiny_state
    paged = op in ("insert_pages", "reset_slots")
    dec = SlotDecoder(cfg, 2, page_size=4 if paged else 0,
                      spec_depth=2 if op == "step_spec" else 0)
    slabs = dec.init_slabs()
    row, first = dec.prefill(state.params, np.asarray([3, 4, 5], np.int32))
    pages = np.eye(1, dec.pages_per_slot, dtype=np.int32)[0]   # [1, 0..]
    toks, live, left = [first, PAD], [True, False], [4, 0]
    call = {
        "insert": lambda: dec.insert(slabs, row, 0),
        "step": lambda: dec.step(state.params, slabs, toks, live)[0],
        "step_many": lambda: dec.step_many(
            state.params, slabs, toks, live, left, 2)[0],
        "step_spec": lambda: dec.step_spec(
            state.params, slabs, toks, live, left, 1)[0],
        "insert_pages": lambda: dec.insert_pages(slabs, row, 0, pages),
        "reset_slots": lambda: dec.reset_slots(slabs, [True, False]),
    }[op]
    assert not slots_lib.consumed(slabs)
    with warnings.catch_warnings(record=True) as caught:
      warnings.simplefilter("always")
      out = call()
      jax.block_until_ready(out)
    assert not [w for w in caught if "donated" in str(w.message).lower()], \
        [str(w.message) for w in caught]
    assert slots_lib.consumed(slabs)
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(out))
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(row))
    assert jax.tree.structure(out) == jax.tree.structure(slabs)


class TestCursorWriteLowerings:
  """The decode step's per-slot cache write has two lowerings
  (``transformer._cache_write``): ``ops.cursor_write``'s DMA kernel where
  Pallas kernels are on, XLA's loop elsewhere. Forced onto the kernel
  (interpret mode here) a ``SlotDecoder`` must emit the loop's tokens and
  leave the loop's slab, bit for bit."""

  @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                           ids=["f32", "bf16"])
  def test_same_tokens_and_same_slab_over_three_dispatches(
      self, monkeypatch, dtype):
    from tensorflowonspark_tpu import ops
    # lane-dense leaves (2 heads x 64 = 128) of whole row tiles (48 rows);
    # LayerNorm and attention pinned off "auto", so that the cache write is
    # the one thing the switch below moves
    cfg = tfm.TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=2, d_model=128, d_ff=256,
        max_seq_len=48, remat=False, dtype=dtype, layer_norm_impl="flax",
        attention_impl="dense")
    params = tfm.create_state(jax.random.PRNGKey(1), cfg, seq_len=16).params
    rng = np.random.RandomState(5)
    # slot 0 reaches max_seq_len after 4 tokens and then stays FROZEN for
    # two more dispatches, slot 1 runs out of budget mid-horizon, slot 2 is
    # never filled: a frozen lane runs at cursor 0 of its own slot (since
    # PR 31; at its last cursor before, where cursor == max must clamp onto
    # the slot's last row, which tests/test_cursor_write.py still holds
    # both lowerings to), and its garbage row must land in the same place
    # in both lowerings
    prompts = [rng.randint(1, 64, (n,)).astype(np.int32) for n in (44, 5)]

    def run(kernels: bool):
      monkeypatch.setattr(ops, "pallas_kernels_enabled", lambda: kernels)
      dec = SlotDecoder(cfg, 3, pad_id=PAD)
      slabs, last = dec.init_slabs(), [PAD] * 3
      for slot, prompt in enumerate(prompts):
        row, last[slot] = dec.prefill(params, prompt)
        slabs = dec.insert(slabs, row, slot)
      active, left, emitted = [True, True, False], [4, 10, 0], []
      for _ in range(3):
        slabs, toks, active, left = dec.step_many(params, slabs, last,
                                                  active, left, 4)
        toks = np.asarray(toks)
        emitted.append(toks)
        last = list(toks[-1])
      return np.stack(emitted), slabs, dec.cursor_writes[4]

    toks_loop, slab_loop, writes_loop = run(False)
    toks_dma, slab_dma, writes_dma = run(True)
    assert writes_loop == (4 * 4, 0)            # K and V of 2 layers x 4
    assert writes_dma == (4 * 4, 4 * 4)
    np.testing.assert_array_equal(toks_dma, toks_loop)
    assert (toks_loop[0, :, 0] != PAD).all() and \
        (toks_loop[1:, :, 0] == PAD).all()       # slot 0 froze at max
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(slab_dma)[0],
                            jax.tree.leaves(slab_loop)):
      np.testing.assert_array_equal(np.asarray(a, np.float32),
                                    np.asarray(b, np.float32),
                                    err_msg=jax.tree_util.keystr(path))


class TestServingEngine:
  def test_mixed_length_parity(self, tiny_state):
    """THE acceptance pin: mixed-length, mixed-budget traffic through a
    3-slot engine is bit-identical per request to single-request
    decodes — across slot reuse, EOS early-exit, and admission order."""
    cfg, state = tiny_state
    rng = np.random.RandomState(42)
    # lengths/budgets drawn from SMALL sets: every parity reference is a
    # fresh (plen, budget) jit of the tiny model, so unconstrained draws
    # made this the slowest test in the module for no extra coverage
    plens = [4, 7, 11, 16]
    buds = [3, 8, 14]
    prompts = [rng.randint(1, 64, (plens[rng.randint(4)],)).astype(np.int32)
               for _ in range(9)]
    budgets = [buds[rng.randint(3)] for _ in range(9)]
    with ServingEngine(state.params, cfg, num_slots=3, eos_id=EOS,
                       pad_id=PAD) as eng:
      rids = [eng.submit(p, max_new_tokens=b)
              for p, b in zip(prompts, budgets)]
      outs = [eng.result(r, timeout=120) for r in rids]
      assert eng.stats["completed"] == len(prompts)
      assert eng.stats["prefills"] == len(prompts)
      assert 0.0 < eng.occupancy <= 1.0
    for p, b, out in zip(prompts, budgets, outs):
      np.testing.assert_array_equal(out,
                                    _reference(state.params, cfg, p, b))

  @pytest.mark.parametrize("stack", ["contiguous", "paged_prefix", "spec"])
  def test_every_slab_dispatch_runs_in_place(self, tiny_state, stack):
    """``slab_dispatches`` counts the engine's calls of slab-returning
    programs (insert / insert_pages / reset_slots / step_many /
    step_spec) and ``slab_in_place`` those that took the slab over: on
    every stack they are equal, and the traffic is still served
    bit-identically from the one buffer."""
    cfg, state = tiny_state
    rng = np.random.RandomState(13)
    prompts = [rng.randint(1, 64, (int(p),)).astype(np.int32)
               for p in (4, 7, 11, 16, 7, 4)]
    kw = {"contiguous": {}, "paged_prefix": dict(page_size=4, prefix_pages=6),
          "spec": dict(spec_depth=3)}[stack]
    with ServingEngine(state.params, cfg, num_slots=3, eos_id=EOS,
                       **kw) as eng:
      outs = eng.generate(prompts, max_new_tokens=8, timeout=120)
      stats = dict(eng.stats)
    assert stats["slab_dispatches"] >= stats["prefills"] \
        + stats["decode_dispatches"] > 0
    assert stats["slab_in_place"] == stats["slab_dispatches"]
    for p, out in zip(prompts, outs):
      np.testing.assert_array_equal(out,
                                    _reference(state.params, cfg, p, 8))

  def test_horizon_invariant(self, tiny_state):
    """The decode horizon is a dispatch-amortization knob, never a
    semantics knob: horizon 1 and 5 produce identical outputs."""
    cfg, state = tiny_state
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 64, (int(p),)).astype(np.int32)
               for p in rng.randint(3, 10, 6)]
    results = {}
    for horizon in (1, 5):
      with ServingEngine(state.params, cfg, num_slots=2, eos_id=EOS,
                         horizon=horizon) as eng:
        outs = eng.generate(prompts, max_new_tokens=9, timeout=120)
      results[horizon] = outs
    for a, b in zip(results[1], results[5]):
      np.testing.assert_array_equal(a, b)

  def test_int8_kv_cache_slot_reuse_parity(self):
    """int8 KV cache under slot reuse: request B decoded in a slot that
    request A just vacated matches B's fresh-cache int8 decode — the
    insert must fully overwrite A's quantized values AND scales."""
    cfg = _tiny(kv_cache_dtype="int8")
    state = tfm.create_state(jax.random.PRNGKey(2), cfg, seq_len=16)
    rng = np.random.RandomState(7)
    a = rng.randint(1, 64, (9,)).astype(np.int32)
    b = rng.randint(1, 64, (5,)).astype(np.int32)
    with ServingEngine(state.params, cfg, num_slots=1, eos_id=EOS) as eng:
      out_a = eng.result(eng.submit(a, max_new_tokens=6), timeout=120)
      out_b = eng.result(eng.submit(b, max_new_tokens=8), timeout=120)
    np.testing.assert_array_equal(out_a,
                                  _reference(state.params, cfg, a, 6))
    np.testing.assert_array_equal(out_b,
                                  _reference(state.params, cfg, b, 8))

  def test_stream_yields_tokens_then_ends(self, tiny_state):
    cfg, state = tiny_state
    with ServingEngine(state.params, cfg, num_slots=1, eos_id=EOS) as eng:
      rid = eng.submit(np.asarray([5, 9], np.int32), max_new_tokens=5)
      toks = list(eng.stream(rid, timeout=60))
    ref = _reference(state.params, cfg, np.asarray([5, 9], np.int32), 5)
    np.testing.assert_array_equal(np.asarray(toks, np.int32), ref[2:])

  def test_poll_and_request_handles(self, tiny_state):
    cfg, state = tiny_state
    with ServingEngine(state.params, cfg, num_slots=1, eos_id=EOS) as eng:
      rid = eng.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=4)
      req = eng.request(rid)
      out = eng.result(rid, timeout=60)
      assert req.latency is not None and req.latency >= 0
      assert out.shape[0] >= 4
      with pytest.raises(KeyError):
        eng.request(rid)            # result() popped the registry entry

  def test_submit_validation(self, tiny_state):
    cfg, state = tiny_state
    eng = ServingEngine(state.params, cfg, num_slots=1)
    with pytest.raises(ValueError, match="max_seq_len"):
      eng.submit(np.zeros(40, np.int32), max_new_tokens=40)
    with pytest.raises(ValueError, match="max_new_tokens"):
      eng.submit(np.zeros(4, np.int32), max_new_tokens=0)
    with pytest.raises(ValueError, match="at least one token"):
      # rejected at submit: a chunk_plan(0) crash inside the loop thread
      # would kill every other in-flight request
      eng.submit(np.asarray([], np.int32), max_new_tokens=4)
    assert eng.alive
    with pytest.raises(ValueError, match="eos_id and pad_id"):
      ServingEngine(state.params, cfg, eos_id=0, pad_id=0)
    with pytest.raises(ValueError, match="horizon"):
      ServingEngine(state.params, cfg, horizon=0)

  def test_stop_fails_queued_requests(self, tiny_state):
    cfg, state = tiny_state
    eng = ServingEngine(state.params, cfg, num_slots=1)   # never started
    rid = eng.submit(np.asarray([1, 2], np.int32), max_new_tokens=2)
    eng.stop()
    with pytest.raises(RuntimeError, match="request %d failed" % rid):
      eng.result(rid, timeout=5)

  def test_env_knobs(self, tiny_state, monkeypatch):
    cfg, state = tiny_state
    monkeypatch.setenv("TOS_SERVE_SLOTS", "7")
    monkeypatch.setenv("TOS_SERVE_HORIZON", "2")
    monkeypatch.setenv("TOS_SERVE_BUCKETS", "16,4,1")
    eng = ServingEngine(state.params, cfg)
    assert eng.num_slots == 7
    assert eng.horizon == 2
    assert eng.buckets == (16, 4, 1)
    # an explicit argument beats the env knob (the num_slots rule)
    assert ServingEngine(state.params, cfg,
                         buckets=(8, 2, 1)).buckets == (8, 2, 1)
    monkeypatch.setenv("TOS_SERVE_BUCKETS", "16,banana")
    with pytest.raises(ValueError, match="TOS_SERVE_BUCKETS"):
      ServingEngine(state.params, cfg)
    monkeypatch.delenv("TOS_SERVE_BUCKETS")
    assert ServingEngine(state.params, cfg).buckets \
        == tuple(DEFAULT_BUCKETS)


class TestAdmissionControl:
  def test_queue_bound_rejects_with_structured_error(self, tiny_state):
    """At TOS_SERVE_MAX_QUEUE the engine REJECTS — structured, with a
    retry-after hint — it never queues unboundedly and never hangs."""
    cfg, state = tiny_state
    eng = ServingEngine(state.params, cfg, num_slots=1, max_queue=2,
                        max_queued_tokens=0)      # not started: queue holds
    eng.submit(np.asarray([1, 2], np.int32), max_new_tokens=4)
    eng.submit(np.asarray([3, 4], np.int32), max_new_tokens=4)
    with pytest.raises(ServingOverloaded) as ei:
      eng.submit(np.asarray([5, 6], np.int32), max_new_tokens=4)
    assert ei.value.queue_depth == 2
    assert ei.value.queued_tokens == 12           # 2 × (2 prompt + 4 budget)
    assert ei.value.retry_after is not None and ei.value.retry_after > 0
    assert not ei.value.draining
    assert eng.stats["rejected"] == 1
    eng.stop()

  def test_token_mass_bound_and_oversized_admission(self, tiny_state):
    cfg, state = tiny_state
    eng = ServingEngine(state.params, cfg, num_slots=1, max_queue=0,
                        max_queued_tokens=20)
    # oversized vs the bound but the queue is empty: admitted (it CAN be
    # served — the bound is about backlog, the feedhub rule)
    eng.submit(np.asarray([1] * 10, np.int32), max_new_tokens=30)
    with pytest.raises(ServingOverloaded,
                       match="TOS_SERVE_MAX_QUEUED_TOKENS"):
      eng.submit(np.asarray([1, 2], np.int32), max_new_tokens=4)
    eng.stop()

  def test_cold_start_retry_after_is_bounded_default(self, tiny_state):
    """Before the first decode completes the tokens/s EMA is 0 — the
    retry_after hint must be the bounded cold-start default, never a
    retry-immediately value that has clients hammering an engine still
    compiling its first dispatch."""
    from tensorflowonspark_tpu.serving import engine as engine_mod
    cfg, state = tiny_state
    eng = ServingEngine(state.params, cfg, num_slots=1, max_queue=1,
                        max_queued_tokens=0)      # not started: cold EMA
    assert eng.tokens_per_sec == 0.0
    eng.submit(np.asarray([1, 2], np.int32), max_new_tokens=4)
    with pytest.raises(ServingOverloaded) as ei:
      eng.submit(np.asarray([3, 4], np.int32), max_new_tokens=4)
    assert ei.value.retry_after >= engine_mod._COLD_RETRY_AFTER
    assert ei.value.retry_after <= 60.0
    eng.stop()

  def test_draining_rejection_carries_retry_after(self, tiny_state):
    """The drain-time turn-away is a retryable condition too (another
    replica will serve it) — it must carry a usable hint, not None."""
    cfg, state = tiny_state
    with ServingEngine(state.params, cfg, num_slots=1) as eng:
      eng._draining = True
      with pytest.raises(ServingOverloaded) as ei:
        eng.submit(np.asarray([1, 2], np.int32), max_new_tokens=4)
      assert ei.value.draining
      assert ei.value.retry_after is not None
      assert ei.value.retry_after > 0

  def test_load_telemetry_properties(self, tiny_state):
    """The fleet router's dispatch inputs: queue depth / token mass /
    occupancy_now reflect the backlog without the obs plane on."""
    cfg, state = tiny_state
    eng = ServingEngine(state.params, cfg, num_slots=2)   # not started
    assert (eng.queue_depth, eng.queued_tokens) == (0, 0)
    assert eng.slots_in_use == 0 and eng.occupancy_now == 0.0
    eng.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=5)
    assert eng.queue_depth == 1 and eng.queued_tokens == 8
    eng.stop()

  def test_env_knobs_register_and_apply(self, tiny_state, monkeypatch):
    cfg, state = tiny_state
    monkeypatch.setenv("TOS_SERVE_MAX_QUEUE", "3")
    monkeypatch.setenv("TOS_SERVE_MAX_QUEUED_TOKENS", "999")
    monkeypatch.setenv("TOS_SERVE_MAX_RESTARTS", "7")
    monkeypatch.setenv("TOS_SERVE_POISON_CRASHES", "4")
    monkeypatch.setenv("TOS_SERVE_TTL", "2.5")
    eng = ServingEngine(state.params, cfg)
    assert eng.max_queue == 3
    assert eng.max_queued_tokens == 999
    assert eng.max_restarts == 7
    assert eng.poison_crashes == 4
    assert eng.default_ttl == 2.5
    # explicit arguments beat the env knobs (the num_slots rule)
    eng2 = ServingEngine(state.params, cfg, max_queue=9,
                         poison_crashes=1, default_ttl=0)
    assert eng2.max_queue == 9 and eng2.poison_crashes == 1
    assert eng2.default_ttl is None


class TestDeadlinesAndCancel:
  def test_dead_on_arrival_rejected_at_submit(self, tiny_state):
    cfg, state = tiny_state
    eng = ServingEngine(state.params, cfg, num_slots=1)
    with pytest.raises(DeadlineExceeded):
      eng.submit(np.asarray([1, 2], np.int32), max_new_tokens=4,
                 deadline=time.monotonic() - 0.01)
    with pytest.raises(ValueError, match="deadline OR ttl"):
      eng.submit(np.asarray([1, 2], np.int32), max_new_tokens=4,
                 deadline=time.monotonic() + 5, ttl=5)
    assert eng.stats["expired"] == 1
    eng.stop()

  def test_queued_expiry_never_takes_a_slot(self, tiny_state):
    """A request whose TTL runs out while queued fails with
    DeadlineExceeded at admission — zero prefills spent on it."""
    cfg, state = tiny_state
    eng = ServingEngine(state.params, cfg, num_slots=1, eos_id=EOS)
    rid = eng.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=4,
                     ttl=0.05)
    time.sleep(0.15)                        # expires while engine is down
    eng.start()
    with pytest.raises(DeadlineExceeded):
      eng.result(rid, timeout=30)
    assert eng.stats["expired"] == 1
    assert eng.stats["prefills"] == 0
    eng.stop()

  def test_cancel_queued_request(self, tiny_state):
    cfg, state = tiny_state
    eng = ServingEngine(state.params, cfg, num_slots=1)   # not started
    rid = eng.submit(np.asarray([1, 2], np.int32), max_new_tokens=4)
    assert eng.cancel(rid, timeout=5.0) is True
    with pytest.raises(RequestCancelled):
      eng.result(rid, timeout=5)
    assert eng.stats["cancelled"] == 1
    assert eng.stats["prefills"] == 0
    eng.stop()

  def test_cancel_inflight_frees_slot_like_eos(self, tiny_state):
    """cancel(rid) on an in-flight request frees its slot at the next
    horizon boundary: the 1-slot engine must go on to serve the next
    request bit-identically."""
    cfg, state = tiny_state
    rng = np.random.RandomState(11)
    a = rng.randint(1, 64, (6,)).astype(np.int32)
    b = rng.randint(1, 64, (4,)).astype(np.int32)
    with ServingEngine(state.params, cfg, num_slots=1, eos_id=None,
                       horizon=2, poll_interval=0.01) as eng:
      # no eos: A runs its full (large) budget unless cancelled
      rid_a = eng.submit(a, max_new_tokens=40)
      deadline = time.monotonic() + 30
      while eng.stats["prefills"] < 1:      # wait until A is in flight
        assert time.monotonic() < deadline
        time.sleep(0.01)
      rid_b = eng.submit(b, max_new_tokens=5)
      assert eng.cancel(rid_a, timeout=30) is True
      with pytest.raises(RequestCancelled):
        eng.result(rid_a, timeout=5)
      out_b = eng.result(rid_b, timeout=60)
      assert eng.stats["cancelled"] == 1
    ref_b = np.asarray(tfm.greedy_generate_kv(
        state.params, cfg, jnp.asarray(b)[None], 5, eos_id=None,
        pad_id=PAD))[0]
    np.testing.assert_array_equal(out_b, ref_b)

  def test_cancel_finished_request_is_noop_true(self, tiny_state):
    cfg, state = tiny_state
    with ServingEngine(state.params, cfg, num_slots=1, eos_id=EOS) as eng:
      rid = eng.submit(np.asarray([1, 2], np.int32), max_new_tokens=3)
      req = eng.request(rid)
      req.done.wait(timeout=60)
      assert eng.cancel(rid, timeout=1.0) is True
      assert eng.result(rid, timeout=5) is not None


class TestDrain:
  def test_drain_finishes_accepted_work_then_stops(self, tiny_state):
    cfg, state = tiny_state
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, 64, (4,)).astype(np.int32)
               for _ in range(5)]
    eng = ServingEngine(state.params, cfg, num_slots=2, eos_id=EOS).start()
    rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    assert eng.drain(timeout=120) is True
    # admission is closed, structurally (a rolling restart sheds no
    # accepted work but accepts no new work)
    with pytest.raises(ServingOverloaded) as ei:
      eng.submit(prompts[0], max_new_tokens=6)
    assert ei.value.draining
    # every accepted request's result is still retrievable after drain
    for p, rid in zip(prompts, rids):
      out = eng.result(rid, timeout=5)
      np.testing.assert_array_equal(out,
                                    _reference(state.params, cfg, p, 6))
    assert not eng.alive                    # stopped: cached callers rebuild

  def test_drain_then_restart_serves_again(self, tiny_state):
    cfg, state = tiny_state
    eng = ServingEngine(state.params, cfg, num_slots=1, eos_id=EOS)
    eng.start()
    assert eng.drain(timeout=60) is True    # nothing in flight: instant
    eng.start()                             # the rolling-restart pattern
    p = np.asarray([4, 5, 6], np.int32)
    out = eng.result(eng.submit(p, max_new_tokens=4), timeout=60)
    np.testing.assert_array_equal(out,
                                  _reference(state.params, cfg, p, 4))
    eng.stop()


class TestFailFast:
  def test_result_on_never_started_engine_fails_fast(self, tiny_state):
    cfg, state = tiny_state
    eng = ServingEngine(state.params, cfg, num_slots=1)
    rid = eng.submit(np.asarray([1, 2], np.int32), max_new_tokens=2)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="never started"):
      eng.result(rid, timeout=600)          # must NOT burn 600s
    assert time.monotonic() - t0 < 5.0
    eng.stop()

  def test_stream_on_never_started_engine_fails_fast(self, tiny_state):
    cfg, state = tiny_state
    eng = ServingEngine(state.params, cfg, num_slots=1)
    rid = eng.submit(np.asarray([1, 2], np.int32), max_new_tokens=2)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="never started"):
      list(eng.stream(rid, timeout=600))
    assert time.monotonic() - t0 < 5.0
    eng.stop()

  def test_submit_after_stop_fails_fast(self, tiny_state):
    cfg, state = tiny_state
    eng = ServingEngine(state.params, cfg, num_slots=1)
    eng.stop()
    with pytest.raises(RuntimeError, match="stopped"):
      eng.submit(np.asarray([1, 2], np.int32), max_new_tokens=2)

  def test_stop_is_idempotent_and_safe_before_start(self, tiny_state):
    cfg, state = tiny_state
    eng = ServingEngine(state.params, cfg, num_slots=1, eos_id=EOS)
    eng.stop()                              # never started: no-op, safe
    eng.stop()                              # idempotent
    eng.start()                             # still startable after stop
    p = np.asarray([7, 8], np.int32)
    out = eng.result(eng.submit(p, max_new_tokens=3), timeout=60)
    np.testing.assert_array_equal(out,
                                  _reference(state.params, cfg, p, 3))
    eng.stop()
    eng.stop()


  def test_kill_seam_fails_waiters_fast_with_cause(self, tiny_state):
    """The terminal-death injection seam (the fleet's chaos kill): the
    engine dies AS IF restarts were exhausted — alive flips, waiters get
    the cause in ms, submit fails fast."""
    cfg, state = tiny_state
    # not started: the queued request cannot win a race with the kill
    eng = ServingEngine(state.params, cfg, num_slots=1)
    rid = eng.submit(np.asarray([1, 2], np.int32), max_new_tokens=32)
    cause = chaos.InjectedFault("killed by test")
    eng.kill(cause)
    assert not eng.alive
    t0 = time.monotonic()
    with pytest.raises(RuntimeError) as ei:
      eng.result(rid, timeout=30)
    assert time.monotonic() - t0 < 5.0
    assert ei.value.__cause__ is cause
    with pytest.raises(RuntimeError):
      eng.submit(np.asarray([3], np.int32), max_new_tokens=2)


class TestPagePool:
  def test_alloc_ref_unref_exactly_once(self):
    pool = PagePool(6)                      # 5 allocatable, page 0 trash
    assert pool.capacity == 5 and pool.free_pages == 5
    pages = pool.alloc(3)
    assert len(pages) == 3 and 0 not in pages
    assert pool.in_use == 3
    pool.ref(pages[0])                      # a second reader (prefix fork)
    assert pool.unref(pages[0]) is False    # still held by the reader
    assert pool.unref(pages[0]) is True     # last ref: freed
    with pytest.raises(ValueError, match="double free"):
      pool.unref(pages[0])
    assert pool.alloc(10) is None           # all-or-nothing
    for p in pages[1:]:
      pool.unref(p)
    assert pool.free_pages == 5

  def test_trash_page_never_allocated_or_freed(self):
    pool = PagePool(3)
    got = pool.alloc(2)
    assert sorted(got) == [1, 2]
    with pytest.raises(ValueError):
      pool.unref(0)
    with pytest.raises(ValueError, match="num_pages"):
      PagePool(1)


class TestPrefixCacheTrie:
  def test_lookup_register_longest_match(self):
    c = PrefixCache(page_size=2, max_pages=8)
    assert c.lookup([1, 2, 3, 4, 5]) == []
    assert c.register([1, 2, 3, 4, 5], [10, 11]) == [10, 11]
    assert c.pages_held == 2
    # same full pages hit; the partial tail page never enters the trie
    assert c.lookup([1, 2, 3, 4, 9, 9]) == [10, 11]
    assert c.lookup([1, 2, 9, 9]) == [10]   # diverges at the second page
    # re-registering an existing path adds nothing; a divergent branch
    # adds only its own page
    assert c.register([1, 2, 3, 4], [20, 21]) == []
    assert c.register([1, 2, 9, 9], [10, 30]) == [30]
    assert c.pages_held == 3

  def test_lru_eviction_leaf_first(self):
    c = PrefixCache(page_size=2, max_pages=2)
    c.register([1, 2, 3, 4], [10, 11])
    c.lookup([1, 2])                        # touch the interior node
    released = c.evict(1)
    assert released == [11]                 # leaf goes first, LRU or not
    assert c.pages_held == 1
    assert c.lookup([1, 2, 3, 4]) == [10]
    assert c.evict(5) == [10]               # drains to empty, no crash
    assert c.evict(1) == []


class TestPagedSlab:
  # prompt lengths / budgets across this module's paged/prefix/spec
  # tests deliberately reuse the (plen, budget) pairs other tests
  # already compiled — the parity oracle is a fresh jit per pair, and
  # novel shapes were the slowest thing in the module

  def test_paged_parity_and_page_release(self, tiny_state):
    """Paged-slab acceptance pin: mixed-length traffic through page
    tables + the pool is bit-identical per request, and every page is
    released once its request completes (refcount accounting)."""
    cfg, state = tiny_state
    rng = np.random.RandomState(13)
    prompts = [rng.randint(1, 64, (int(p),)).astype(np.int32)
               for p in (4, 7, 11, 16, 7, 4)]
    budgets = [3, 8, 14, 8, 3, 8]
    with ServingEngine(state.params, cfg, num_slots=3, eos_id=EOS,
                       page_size=4) as eng:
      rids = [eng.submit(p, max_new_tokens=b)
              for p, b in zip(prompts, budgets)]
      outs = [eng.result(r, timeout=120) for r in rids]
      assert eng.kv_pages_in_use == 0       # everything returned
    for p, b, out in zip(prompts, budgets, outs):
      np.testing.assert_array_equal(out,
                                    _reference(state.params, cfg, p, b))

  def test_tight_pool_waits_for_pages_then_serves(self, tiny_state):
    """More slots than the pool can host at once: requests WAIT in the
    queue for completions to free pages (never fail, never corrupt) —
    the slot-count-exceeds-HBM regime paging exists for."""
    cfg, state = tiny_state
    rng = np.random.RandomState(17)
    prompts = [rng.randint(1, 64, (int(p),)).astype(np.int32)
               for p in (16, 11, 7, 4)]
    # the length-16 request needs ceil((16+8)/4)=6 pages; 12 allocatable
    # pages host at most two such concurrently across 4 slots
    with ServingEngine(state.params, cfg, num_slots=4, eos_id=EOS,
                       page_size=4, num_pages=13) as eng:
      rids = [eng.submit(p, max_new_tokens=8) for p in prompts]
      outs = [eng.result(r, timeout=120) for r in rids]
    for p, out in zip(prompts, outs):
      np.testing.assert_array_equal(out,
                                    _reference(state.params, cfg, p, 8))

  def test_oversized_for_pool_rejected_at_submit(self, tiny_state):
    cfg, state = tiny_state
    eng = ServingEngine(state.params, cfg, num_slots=1, page_size=4,
                        num_pages=4)
    with pytest.raises(ValueError, match="KV pages"):
      eng.submit(np.arange(1, 20, dtype=np.int32), max_new_tokens=20)
    eng.stop()

  def test_env_knobs_register_and_apply(self, tiny_state, monkeypatch):
    cfg, state = tiny_state
    monkeypatch.setenv("TOS_SERVE_PAGE_SIZE", "4")
    monkeypatch.setenv("TOS_SERVE_NUM_PAGES", "20")
    monkeypatch.setenv("TOS_SERVE_PREFIX_PAGES", "6")
    monkeypatch.setenv("TOS_SERVE_SPEC_DEPTH", "3")
    monkeypatch.setenv("TOS_SERVE_SPEC_LAYERS", "1")
    eng = ServingEngine(state.params, cfg)
    assert eng.page_size == 4
    assert eng.decoder.paged and eng.decoder.num_pages == 20
    assert eng.prefix_pages == 6
    assert eng.spec_depth == 3 and eng.decoder.spec_layers == 1
    # explicit arguments beat the env knobs (the num_slots rule)
    eng2 = ServingEngine(state.params, cfg, page_size=0, prefix_pages=0,
                         spec_depth=0)
    assert not eng2.decoder.paged and eng2.spec_depth == 0

  def test_prefix_cache_requires_paging(self, tiny_state):
    cfg, state = tiny_state
    with pytest.raises(ValueError, match="TOS_SERVE_PAGE_SIZE"):
      ServingEngine(state.params, cfg, prefix_pages=4)


class TestPrefixSharing:
  def test_shared_prefix_parity_hits_release_and_drain(self, tiny_state):
    """Requests sharing a system prefix prefill it once (prefix_hits),
    stay bit-identical, and after every request completes the ONLY
    pages still allocated are the prefix cache's own refs — completion
    released each request's refs exactly once. A second wave then rides
    `drain()`: admission closes, accepted work finishes (zero shed),
    and the drain path releases its ref-counted pages exactly once too
    (the loud-double-free PagePool would raise otherwise)."""
    cfg, state = tiny_state
    rng = np.random.RandomState(23)
    prefix = rng.randint(1, 64, (12,)).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.randint(1, 64, (n,)).astype(np.int32)])
               for n in (3, 5, 2, 6)]
    eng = ServingEngine(state.params, cfg, num_slots=2, eos_id=EOS,
                        page_size=4, prefix_pages=8).start()
    rids = [eng.submit(p, max_new_tokens=8) for p in prompts]
    outs = [eng.result(r, timeout=120) for r in rids]
    assert eng.stats["prefix_hits"] >= len(prompts) - 1
    # exactly-once release: live pages == the cache's holdings
    assert eng.kv_pages_in_use == eng._prefix.pages_held > 0
    drain_rids = [eng.submit(p, max_new_tokens=8) for p in prompts[:2]]
    assert eng.drain(timeout=120) is True
    for p, out in zip(prompts, outs):
      np.testing.assert_array_equal(out,
                                    _reference(state.params, cfg, p, 8))
    for p, rid in zip(prompts[:2], drain_rids):
      np.testing.assert_array_equal(eng.result(rid, timeout=5),
                                    _reference(state.params, cfg, p, 8))
    assert not eng.alive

  def test_eviction_under_budget_keeps_parity(self, tiny_state):
    """A prefix budget too small for the traffic evicts LRU pages
    (counter moves) without ever corrupting decodes — ref-counted pages
    survive until their last reader finishes."""
    cfg, state = tiny_state
    rng = np.random.RandomState(29)
    pre_a = rng.randint(1, 64, (12,)).astype(np.int32)
    pre_b = rng.randint(1, 64, (12,)).astype(np.int32)
    prompts = []
    for pre in (pre_a, pre_b, pre_a, pre_b):
      prompts.append(np.concatenate(
          [pre, rng.randint(1, 64, (3,)).astype(np.int32)]))
    with ServingEngine(state.params, cfg, num_slots=1, eos_id=EOS,
                       page_size=4, prefix_pages=3) as eng:
      outs = [eng.result(eng.submit(p, max_new_tokens=8), timeout=120)
              for p in prompts]
      assert eng.stats["prefix_evictions"] > 0
      assert eng._prefix.pages_held <= 3
    for p, out in zip(prompts, outs):
      np.testing.assert_array_equal(out,
                                    _reference(state.params, cfg, p, 8))


class TestSpeculativeDecode:
  def test_spec_parity_and_counters(self, tiny_state):
    """Self-speculative decode is a SPEED knob, never a semantics knob:
    outputs stay bit-identical to single-request decodes while the
    accept/reject counters show the mechanism actually ran."""
    cfg, state = tiny_state
    rng = np.random.RandomState(37)
    prompts = [rng.randint(1, 64, (int(p),)).astype(np.int32)
               for p in (4, 7, 11, 16, 7)]
    budgets = [3, 8, 14, 8, 3]
    with ServingEngine(state.params, cfg, num_slots=3, eos_id=EOS,
                       spec_depth=3) as eng:
      rids = [eng.submit(p, max_new_tokens=b)
              for p, b in zip(prompts, budgets)]
      outs = [eng.result(r, timeout=120) for r in rids]
      assert eng.stats["spec_accepted"] + eng.stats["spec_rejected"] > 0
    for p, b, out in zip(prompts, budgets, outs):
      np.testing.assert_array_equal(out,
                                    _reference(state.params, cfg, p, b))

  def test_full_stack_parity(self, tiny_state):
    """Paged slab + prefix sharing + speculation COMPOSED keep the
    bit-identical contract (the combined-stack acceptance gate)."""
    cfg, state = tiny_state
    rng = np.random.RandomState(41)
    prefix = rng.randint(1, 64, (12,)).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.randint(1, 64, (n,)).astype(np.int32)])
               for n in (3, 5, 4, 6)]
    with ServingEngine(state.params, cfg, num_slots=3, eos_id=EOS,
                       page_size=4, prefix_pages=8, spec_depth=2) as eng:
      rids = [eng.submit(p, max_new_tokens=8) for p in prompts]
      outs = [eng.result(r, timeout=120) for r in rids]
    for p, out in zip(prompts, outs):
      np.testing.assert_array_equal(out,
                                    _reference(state.params, cfg, p, 8))

  def test_spec_overshoot_at_max_seq_len_keeps_parity(self, tiny_state):
    """A verify window may transiently overshoot max_seq_len on a lane
    whose remaining budget < spec_depth at the cap. The overflow writes
    must DROP (contiguous: OOB scatter; paged: forced to the trash
    page) — a clamped/clipped write would overwrite live attended KV
    below the cursor and break bit-parity. Regression for the review
    finding: prompt+budget pinned exactly at max_seq_len, depth 6."""
    cfg, state = tiny_state                 # max_seq_len = 48
    rng = np.random.RandomState(47)
    prompt = rng.randint(1, 64, (34,)).astype(np.int32)
    budget = cfg.max_seq_len - len(prompt)  # 14: flush against the cap
    ref = _reference(state.params, cfg, prompt, budget)
    for paged in (dict(), dict(page_size=4)):
      with ServingEngine(state.params, cfg, num_slots=1, eos_id=EOS,
                         spec_depth=6, **paged) as eng:
        out = eng.result(eng.submit(prompt, max_new_tokens=budget),
                         timeout=120)
      np.testing.assert_array_equal(out, ref, err_msg=str(paged))

  @pytest.mark.slow
  def test_spec_depth_invariant(self, tiny_state):
    """Like the horizon: spec depth changes dispatch shape only —
    spec off and spec depth 2 emit identical streams.

    Marked slow (tier-1 budget audit): two full engine runs over the
    mixed-length prompt set; spec parity stays tier-1-pinned by the
    overshoot test below and the models-layer speculative-decode
    exactness test. Runs via `make test`."""
    cfg, state = tiny_state
    rng = np.random.RandomState(43)
    prompts = [rng.randint(1, 64, (int(p),)).astype(np.int32)
               for p in (4, 7, 11, 16)]
    results = {}
    for depth in (0, 2):
      with ServingEngine(state.params, cfg, num_slots=2, eos_id=EOS,
                         spec_depth=depth) as eng:
        results[depth] = eng.generate(prompts, max_new_tokens=8,
                                      timeout=120)
    for a, b in zip(results[0], results[2]):
      np.testing.assert_array_equal(a, b)


@pytest.mark.chaos
class TestServingChaos:
  """TOS_CHAOS_SERVE-driven recovery proofs (make chaos-serve): the
  self-healing contract is exercised under injected faults, not assumed.
  Chaos counters are per-process — every test resets them."""

  @pytest.fixture(autouse=True)
  def _fresh_chaos(self, monkeypatch):
    chaos.reset()
    yield
    monkeypatch.delenv(chaos.ENV_SERVE, raising=False)
    chaos.reset()

  def test_decode_crash_replays_bit_identical(self, tiny_state,
                                              monkeypatch):
    """THE acceptance pin: a decode-dispatch crash mid-run is healed by
    replaying every in-flight request from its prompt — outputs stay
    bit-identical to uninjured single-request decodes, the engine stays
    alive, and the restart/replay counters fire. Rides the same run
    (one crash cycle is expensive): the detailed TIMING LEDGER reports
    the replay with a first-token stamp from BEFORE the recovery — the
    integration twin of the replay-never-resets-first_token unit pin."""
    cfg, state = tiny_state
    rng = np.random.RandomState(21)
    prompts = [rng.randint(1, 64, (int(p),)).astype(np.int32)
               for p in (4, 7, 5, 9, 6, 8)]
    monkeypatch.setenv(chaos.ENV_SERVE, "decode#2:raise")
    with ServingEngine(state.params, cfg, num_slots=2, eos_id=EOS,
                       poison_crashes=3, restart_backoff=0.01) as eng:
      outs = eng.generate(prompts, max_new_tokens=8, timeout=120,
                          detailed=True)
      stats = dict(eng.stats)
      assert eng.alive
      log = list(eng.restart_log)
    assert stats["engine_restarts"] == 1
    assert stats["replays"] >= 1
    assert stats["replay_mismatches"] == 0
    assert stats["poisoned"] == 0
    # the slab is donated to every program: the crash's victims replay on
    # a FRESH slab, and every dispatch on either side of it ran in place
    assert stats["slab_in_place"] == stats["slab_dispatches"] > 0
    assert len(log) == 1 and log[0]["duration_s"] >= 0.01
    replayed = [o for o in outs if o["timing"]["replays"]]
    assert replayed                  # the crash hit someone in flight
    for o in replayed:
      t = o["timing"]
      # the first token predates the recovery: replay didn't reset it
      assert t["first_token"] is not None
      assert t["first_token"] <= log[0]["t"]
    for p, o in zip(prompts, outs):
      np.testing.assert_array_equal(
          o["tokens"], _reference(state.params, cfg, p, 8))

  def test_decode_crash_replays_paged_stack_bit_identical(
      self, tiny_state, monkeypatch):
    """Crash-replay OVER THE PAGED SLAB (+ prefix cache + spec): the
    recovery rebuilds the page pool, page tables and prefix trie from
    nothing and replays every in-flight request — outputs stay
    bit-identical with stream dedup, and the rebuilt pool's accounting
    balances (no pages leaked across the crash)."""
    cfg, state = tiny_state
    rng = np.random.RandomState(51)
    prefix = rng.randint(1, 64, (12,)).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.randint(1, 64, (n,)).astype(np.int32)])
               for n in (3, 5, 4, 6, 2, 3)]
    monkeypatch.setenv(chaos.ENV_SERVE, "decode#2:raise")
    with ServingEngine(state.params, cfg, num_slots=2, eos_id=EOS,
                       page_size=4, prefix_pages=6, spec_depth=2,
                       poison_crashes=3, restart_backoff=0.01) as eng:
      outs = eng.generate(prompts, max_new_tokens=8, timeout=120)
      stats = dict(eng.stats)
      assert eng.alive
      # the post-crash pool balances: only the rebuilt prefix cache
      # still holds pages once every request finished
      assert eng.kv_pages_in_use == eng._prefix.pages_held
    assert stats["engine_restarts"] == 1
    assert stats["replays"] >= 1
    assert stats["replay_mismatches"] == 0
    assert stats["slab_in_place"] == stats["slab_dispatches"] > 0
    for p, out in zip(prompts, outs):
      np.testing.assert_array_equal(
          out, _reference(state.params, cfg, p, 8))

  def test_stream_is_deduplicated_across_crash(self, tiny_state,
                                               monkeypatch):
    """A stream() consumer must see every position exactly once even
    when the crash forces the engine to regenerate the prefix."""
    cfg, state = tiny_state
    p = np.asarray([3, 9, 4, 1], np.int32)
    monkeypatch.setenv(chaos.ENV_SERVE, "decode#2:raise")
    with ServingEngine(state.params, cfg, num_slots=1, eos_id=EOS,
                       horizon=1, poison_crashes=3,
                       restart_backoff=0.01) as eng:
      rid = eng.submit(p, max_new_tokens=10)
      toks = list(eng.stream(rid, timeout=120))
      assert eng.stats["engine_restarts"] == 1
      assert eng.stats["replays"] == 1
    ref = _reference(state.params, cfg, p, 10)
    np.testing.assert_array_equal(np.asarray(toks, np.int32),
                                  ref[len(p):])

  def test_prefill_poison_request_isolated(self, tiny_state, monkeypatch):
    """A request that deterministically crashes its own prefill (the
    per-prompt-length chaos index) is failed as PoisonedRequest after
    poison_crashes consecutive crashes — while its neighbors replay and
    complete bit-identically. No crash loop, engine stays alive."""
    cfg, state = tiny_state
    rng = np.random.RandomState(31)
    good_a = rng.randint(1, 64, (5,)).astype(np.int32)
    poison = rng.randint(1, 64, (13,)).astype(np.int32)   # unique length
    good_b = rng.randint(1, 64, (8,)).astype(np.int32)
    monkeypatch.setenv(chaos.ENV_SERVE,
                       "prefill@13#1:raise,prefill@13#2:raise")
    with ServingEngine(state.params, cfg, num_slots=2, eos_id=EOS,
                       poison_crashes=2, restart_backoff=0.01) as eng:
      rid_a = eng.submit(good_a, max_new_tokens=6)
      rid_p = eng.submit(poison, max_new_tokens=6)
      rid_b = eng.submit(good_b, max_new_tokens=6)
      out_a = eng.result(rid_a, timeout=120)
      out_b = eng.result(rid_b, timeout=120)
      with pytest.raises(PoisonedRequest,
                         match="consecutive engine crashes"):
        eng.result(rid_p, timeout=120)
      assert eng.alive                      # healed, not dead
      assert eng.stats["engine_restarts"] == 2
      assert eng.stats["poisoned"] == 1
      # the poison verdict chains the actual crash cause
      assert eng.stats["replay_mismatches"] == 0
    np.testing.assert_array_equal(
        out_a, _reference(state.params, cfg, good_a, 6))
    np.testing.assert_array_equal(
        out_b, _reference(state.params, cfg, good_b, 6))

  def test_stall_blows_deadline_and_frees_slot(self, tiny_state,
                                               monkeypatch):
    """A stall fault (hung-device stand-in) makes an in-flight request
    miss its deadline: it is reaped at the horizon boundary — freeing
    the slot exactly like EOS — and a later request completes."""
    cfg, state = tiny_state
    rng = np.random.RandomState(41)
    victim = rng.randint(1, 64, (6,)).astype(np.int32)
    healthy = rng.randint(1, 64, (4,)).astype(np.int32)
    with ServingEngine(state.params, cfg, num_slots=1, eos_id=None,
                       horizon=2, poll_interval=0.01) as eng:
      # warm every jit (prefill buckets for both lengths + the fused
      # step) so the timed phase measures the stall, not compilation
      eng.generate([victim, healthy], max_new_tokens=2, timeout=120)
      monkeypatch.setenv(chaos.ENV_SERVE, "decode#1:stall:0.5")
      chaos.reset()
      rid_v = eng.submit(victim, max_new_tokens=40, ttl=0.2)
      with pytest.raises(DeadlineExceeded):
        eng.result(rid_v, timeout=60)
      monkeypatch.delenv(chaos.ENV_SERVE)
      chaos.reset()
      rid_h = eng.submit(healthy, max_new_tokens=4)
      out_h = eng.result(rid_h, timeout=60)
      assert eng.stats["expired"] == 1
    ref_h = np.asarray(tfm.greedy_generate_kv(
        state.params, cfg, jnp.asarray(healthy)[None], 4, eos_id=None,
        pad_id=PAD))[0]
    np.testing.assert_array_equal(out_h, ref_h)

  def test_terminal_death_fails_everyone_fast(self, tiny_state,
                                              monkeypatch):
    """max_restarts=0: the first crash is terminal. Every waiter gets
    the root cause promptly, and submit fails fast instead of orphaning
    a request behind the dying loop's drain (the PR race fix)."""
    cfg, state = tiny_state
    p = np.asarray([2, 3, 4], np.int32)
    monkeypatch.setenv(chaos.ENV_SERVE, "decode#1:raise")
    eng = ServingEngine(state.params, cfg, num_slots=1, eos_id=EOS,
                        max_restarts=0).start()
    try:
      rid = eng.submit(p, max_new_tokens=8)
      t0 = time.monotonic()
      with pytest.raises(RuntimeError, match="request %d failed" % rid):
        eng.result(rid, timeout=600)
      assert time.monotonic() - t0 < 30.0   # not the 600s timeout
      assert not eng.alive
      # submit now fails immediately with the loop's root cause
      with pytest.raises(RuntimeError, match="serving loop died") as ei:
        eng.submit(p, max_new_tokens=2)
      assert isinstance(ei.value.__cause__, chaos.InjectedFault)
    finally:
      eng.stop()


class TestServingPredictFn:
  def test_ragged_batch_routes_through_engine(self, tiny_state):
    """TFModel.transform's ragged-column fallback: variable-length
    prompt rows decode per-request through the engine and come back
    right-padded to a rectangle."""
    cfg, state = tiny_state
    fn = tfm.make_serving_predict_fn(cfg, 5, eos_id=EOS, pad_id=PAD,
                                     num_slots=2)
    prompts = [np.asarray([1, 2, 3], np.int32),
               np.asarray([4, 5], np.int32),
               np.asarray([9, 8, 7, 6, 5], np.int32)]
    col = np.empty(3, object)
    col[:] = prompts
    out = fn(state.params, {"x": col})["tokens"]
    assert out.dtype == np.int32 and out.ndim == 2
    for i, p in enumerate(prompts):
      ref = _reference(state.params, cfg, p, 5)
      np.testing.assert_array_equal(out[i, :len(ref)], ref)
      assert (out[i, len(ref):] == PAD).all()

  def test_equal_length_object_column_stacks(self, tiny_state):
    """An object column whose rows happen to share one length is NOT
    ragged: it must stack and ride the fixed-shape path instead of
    crashing np.asarray (numpy refuses int conversion of object rows)."""
    cfg, state = tiny_state
    fn = tfm.make_serving_predict_fn(cfg, 4, eos_id=EOS, pad_id=PAD)
    col = np.empty(2, object)
    col[:] = [np.asarray([1, 2, 3], np.int32),
              np.asarray([4, 5, 6], np.int32)]
    out = fn(state.params, {"x": col})["tokens"]
    ref = np.asarray(tfm.greedy_generate_kv(
        state.params, cfg, jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32),
        4, eos_id=EOS, pad_id=PAD))
    np.testing.assert_array_equal(out, ref)

  def test_rectangular_batch_keeps_fixed_path(self, tiny_state):
    cfg, state = tiny_state
    fn = tfm.make_serving_predict_fn(cfg, 4, eos_id=EOS, pad_id=PAD)
    batch = np.asarray([[1, 2, 3], [4, 5, 6]], np.int32)
    out = fn(state.params, {"x": batch})["tokens"]
    ref = np.asarray(tfm.greedy_generate_kv(
        state.params, cfg, jnp.asarray(batch), 4, eos_id=EOS, pad_id=PAD))
    np.testing.assert_array_equal(out, ref)

  def test_ragged_path_ignores_client_admission_bounds(self, tiny_state,
                                                       monkeypatch):
    """The transform path's internal engine must NOT inherit the
    client-facing admission bounds: a ragged partition larger than
    TOS_SERVE_MAX_QUEUE served fine before the robustness PR and must
    keep serving — bounds are for direct ServingEngine users."""
    cfg, state = tiny_state
    monkeypatch.setenv("TOS_SERVE_MAX_QUEUE", "2")
    monkeypatch.setenv("TOS_SERVE_MAX_QUEUED_TOKENS", "8")
    fn = tfm.make_serving_predict_fn(cfg, 3, eos_id=EOS, pad_id=PAD,
                                     num_slots=1)
    rng = np.random.RandomState(17)
    prompts = [rng.randint(1, 64, (n,)).astype(np.int32)
               for n in (3, 5, 4, 6, 3, 5)]       # 6 rows >> bound of 2
    col = np.empty(len(prompts), object)
    col[:] = prompts
    out = fn(state.params, {"x": col})["tokens"]
    for i, p in enumerate(prompts):
      ref = _reference(state.params, cfg, p, 3)
      np.testing.assert_array_equal(out[i, :len(ref)], ref)

  def test_ragged_sampling_rejected(self, tiny_state):
    cfg, state = tiny_state
    fn = tfm.make_serving_predict_fn(cfg, 4, temperature=0.7, eos_id=EOS)
    col = np.empty(2, object)
    col[:] = [np.asarray([1, 2], np.int32), np.asarray([3], np.int32)]
    with pytest.raises(ValueError, match="greedy-only"):
      fn(state.params, {"x": col})


# --- request timing ledger + trace linkage (PR 14) ---------------------------


class TestTimingLedger:
  def test_request_stamps_and_derived_fields(self):
    r = Request(np.asarray([1, 2, 3], np.int32), 4)
    assert r.trace_id and len(r.trace_id) == 16
    assert r.ttft is None and r.queue_wait is None and r.tpot is None
    r.started_at = r.submitted_at + 0.5
    r.emit(5)
    assert r.first_token_at is not None
    assert r.ttft == pytest.approx(
        r.first_token_at - r.submitted_at)
    assert r.queue_wait == pytest.approx(0.5)
    r.emit(6)
    r.finish(None)
    assert r.tpot == pytest.approx(r.finished_at - r.first_token_at)
    t = r.timing()
    assert t["generated"] == 2 and t["replays"] == 0
    assert t["trace_id"] == r.trace_id
    assert t["ttft"] == r.ttft and t["e2e"] == r.latency

  def test_replay_never_resets_first_token(self):
    """THE satellite pin: a crash replay regenerates positions the
    client already holds — the client saw its first token ONCE, and
    that moment is what TTFT measures."""
    r = Request(np.asarray([1, 2], np.int32), 4)
    r.emit(9)
    stamp = r.first_token_at
    time.sleep(0.01)
    r.begin_replay()
    assert r.emit(9) is True          # suppressed, parity holds
    assert r.first_token_at == stamp
    assert r.replays == 1
    assert r.timing()["replays"] == 1

  def test_submit_joins_an_existing_trace(self):
    r = Request(np.asarray([1], np.int32), 2, trace_id="deadbeefcafe0001")
    assert r.trace_id == "deadbeefcafe0001"

  def test_generate_detailed_returns_ledger_with_parity(self, tiny_state):
    cfg, state = tiny_state
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 64, (n,)).astype(np.int32)
               for n in (4, 6, 5)]
    with ServingEngine(state.params, cfg, num_slots=2, eos_id=EOS) as eng:
      outs = eng.generate(prompts, max_new_tokens=6, timeout=120,
                          detailed=True)
    assert len(outs) == 3
    traces = set()
    for p, o in zip(prompts, outs):
      np.testing.assert_array_equal(
          o["tokens"], _reference(state.params, cfg, p, 6))
      t = o["timing"]
      traces.add(o["trace_id"])
      assert t["trace_id"] == o["trace_id"]
      assert t["submitted"] <= t["admitted"] <= t["prefill_done"] \
          <= t["first_token"] <= t["finished"]
      assert t["ttft"] is not None and t["ttft"] >= 0
      assert t["queue_wait"] is not None and t["e2e"] >= t["ttft"]
      assert t["replays"] == 0
    assert len(traces) == 3            # one fresh trace per request


class TestTraceLinkage:
  @pytest.fixture(autouse=True)
  def _recorder(self):
    from tensorflowonspark_tpu.obs import spans as spans_mod
    self.rec = spans_mod.activate()
    yield
    spans_mod.deactivate()

  def test_every_request_span_carries_its_trace(self, tiny_state):
    """The tentpole invariant: every span a request touches — queue
    wait, prefill (+ per-chunk), slot-attributed decode, stream — is
    stamped with THAT request's trace id, and ids never cross."""
    cfg, state = tiny_state
    rng = np.random.RandomState(9)
    prompts = [rng.randint(1, 64, (n,)).astype(np.int32)
               for n in (4, 6)]
    with ServingEngine(state.params, cfg, num_slots=2, eos_id=EOS) as eng:
      rids = [eng.submit(p, max_new_tokens=5) for p in prompts]
      traces = [eng._requests[rid].trace_id for rid in rids]
      for rid in rids:
        list(eng.stream(rid, timeout=120))
    recs = self.rec.drain()
    by_trace = {}
    for r in recs:
      if r.get("trace"):
        by_trace.setdefault(r["trace"], set()).add(r["name"])
    assert set(traces) == set(by_trace)
    for t in traces:
      assert {"serve.queue", "serve.prefill", "serve.prefill.chunk",
              "serve.decode.slot", "serve.stream"} <= by_trace[t]
    # and no serve.* request span leaked WITHOUT a trace stamp
    for r in recs:
      if r["name"] in ("serve.queue", "serve.prefill",
                       "serve.prefill.chunk", "serve.decode.slot",
                       "serve.stream"):
        assert r.get("trace"), r["name"]

  def test_trace_detail_knob_drops_highvolume_spans(self, tiny_state,
                                                    monkeypatch):
    """TOS_OBS_TRACE_DETAIL=0 keeps the request trace (queue/prefill/
    stream) but drops the per-lane decode + per-chunk prefill records —
    the span-volume relief valve for large deployments."""
    cfg, state = tiny_state
    monkeypatch.setenv("TOS_OBS_TRACE_DETAIL", "0")
    p = np.asarray([3, 5, 9, 11], np.int32)
    with ServingEngine(state.params, cfg, num_slots=1, eos_id=EOS) as eng:
      rid = eng.submit(p, max_new_tokens=4)
      list(eng.stream(rid, timeout=120))
    names = {r["name"] for r in self.rec.drain() if r.get("trace")}
    assert {"serve.queue", "serve.prefill", "serve.stream"} <= names
    assert "serve.decode.slot" not in names
    assert "serve.prefill.chunk" not in names


class TestRouterScoringReads:
  def test_mid_admission_request_counts_as_backlog(self, tiny_state):
    """The fleet router's scoring blind spot, pinned: a request the
    loop has popped for admission (prefill in progress) must still
    count in queue_depth/queued_tokens — (queue 0, occupancy 0) on a
    replica mid-prefill reads as 'completely idle' and double-books it
    (found as a routing flip in the failover-hop chaos test)."""
    cfg, state = tiny_state
    eng = ServingEngine(state.params, cfg, num_slots=1, eos_id=EOS)
    req = Request(np.asarray([1, 2, 3], np.int32), 5)
    assert eng.queue_depth == 0 and eng.queued_tokens == 0
    eng._mark_admitting(req)        # the loop's on_pop hook
    assert eng.queue_depth == 1
    assert eng.queued_tokens == len(req.prompt) + req.max_new_tokens
    eng._admitting = None
    assert eng.queue_depth == 0 and eng.queued_tokens == 0
