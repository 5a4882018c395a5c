"""The order of a serving pass (``ServingEngine._pass``): with a live lane the
decode step is dispatched FIRST, one admission's chunks and insert are queued
behind it unread, the step's tokens are read and harvested, and only then the
admission's first token — CPU, toy width, a recording decoder, no clock.

Most tests drive ``_pass`` by hand on an engine whose thread never starts: a
pass is then one deterministic sequence of calls, and what the decoder was
asked for, in which order, is the whole observation.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.obs import spans as spans_mod
from tensorflowonspark_tpu.serving import (
    DeadlineExceeded, RequestCancelled, ServingEngine)
from tensorflowonspark_tpu.utils import chaos

PAD = 0
PHASE_KEYS = ("t_reap_s", "t_idle_s", "t_admit_s", "t_prefill_s",
              "t_prefill_sync_s", "t_insert_s", "t_decode_prep_s",
              "t_decode_dispatch_s", "t_decode_fetch_s",
              "t_decode_harvest_s")
EMPTY_KEYS = tuple(spans_mod.empty_key(k) for k in PHASE_KEYS)


@pytest.fixture(scope="module")
def tiny():
  cfg = tfm.TransformerConfig(vocab_size=64, num_layers=2, num_heads=2,
                              d_model=32, d_ff=64, max_seq_len=48,
                              remat=False, dtype=jnp.float32)
  return cfg, tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=16)


def _prompt(n, seed):
  return np.random.RandomState(seed).randint(1, 64, (n,)).astype(np.int32)


def _reference(params, cfg, prompt, budget, eos_id=None):
  """Single-request decode truncated at its stop: the parity oracle."""
  out = np.asarray(tfm.greedy_generate_kv(
      params, cfg, jnp.asarray(prompt)[None], budget, eos_id=eos_id,
      pad_id=PAD))[0]
  gen = out[len(prompt):]
  stops = np.where(gen == eos_id)[0] if eos_id is not None else ()
  stop = (int(stops[0]) + 1) if len(stops) else budget
  return np.concatenate([prompt, gen[:stop]])


class _Read(object):
  """A device array whose read is an event of the log; ``hook[0]``, where
  set, runs inside the read."""

  def __init__(self, array, log, event, hook):
    self._array, self._log, self._event = array, log, event
    self._hook = hook

  def __array__(self, dtype=None, copy=None):
    self._log.append(self._event)
    if self._hook[0] is not None:
      self._hook[0]()
    return np.asarray(self._array, dtype=dtype)


def _record(eng, log):
  """Wrap the engine's decoder so that every dispatch and every read of a
  pass lands in ``log``, in order. Returns ``hook``: what a test puts in
  ``hook[0]`` runs inside the read of a decode step's tokens, the one point
  of a pass at which an admission may be in flight unread."""
  dec, hook = eng.decoder, [None]

  def stepping(step):
    def logged(*a, **kw):
      out = step(*a, **kw)
      log.append("step")
      return (out[0], _Read(out[1], log, "read step", hook)) + out[2:]
    return logged

  def inserting(insert):
    def logged(slabs, row, slot, *a, **kw):
      log.append("insert %d" % slot)
      return insert(slabs, row, slot, *a, **kw)
    return logged

  chunks, first = dec.prefill_chunks, dec.prefill_first

  def prefill_chunks(params, prompt, *a, **kw):
    out = chunks(params, prompt, *a, **kw)
    log.append("chunks %d" % len(prompt))
    return out

  def prefill_first(*a, **kw):
    log.append("read first")
    return first(*a, **kw)

  dec.step_many = stepping(dec.step_many)
  dec.step_spec = stepping(dec.step_spec)
  dec.prefill_chunks, dec.prefill_first = prefill_chunks, prefill_first
  dec.insert = inserting(dec.insert)
  dec.insert_pages = inserting(dec.insert_pages)
  return hook


def _engine(tiny, **kw):
  """An engine that never starts its thread: the test calls ``_pass``."""
  cfg, state = tiny
  kw.setdefault("num_slots", 2)
  kw.setdefault("eos_id", None)
  kw.setdefault("poll_interval", 0.001)
  return ServingEngine(state.params, cfg, horizon=4, **kw)


def _one_pass(eng, log):
  del log[:]
  eng._pass()
  return list(log)


def _run_out(eng, reqs, recover=False, limit=200):
  """Pass after pass (recovering from a crash like ``_loop`` does) until
  every request is done."""
  for _ in range(limit):
    if all(r.done.is_set() for r in reqs):
      return
    try:
      eng._pass()
    except Exception as e:  # noqa: BLE001 - what _loop's handler does
      if not recover:
        raise
      assert eng._recover(e)
  raise AssertionError("requests still unfinished after %d passes" % limit)


def _submit(eng, prompt, budget):
  return eng.request(eng.submit(prompt, max_new_tokens=budget))


def _assert_parity(tiny, eng, reqs, eos_id=None):
  cfg, state = tiny
  for r in reqs:
    assert r.error is None
    np.testing.assert_array_equal(
        r.output(), _reference(state.params, cfg, r.prompt,
                               r.max_new_tokens, eos_id=eos_id))
  assert eng.stats["replay_mismatches"] == 0


# -- the order of a pass ------------------------------------------------------


def test_a_pass_with_no_live_lane_admits_as_it_always_did(tiny):
  """An idle engine has no step to queue behind: dispatch, read, insert,
  one request at a time, and no decode step in the pass."""
  eng, log = _engine(tiny), []
  _record(eng, log)
  a = _submit(eng, _prompt(5, 1), 6)
  b = _submit(eng, _prompt(7, 2), 9)
  assert _one_pass(eng, log) == ["chunks 5", "read first", "insert 0",
                                 "chunks 7", "read first", "insert 1"]
  assert eng._slots == [a, b] and eng._admitting is None
  assert eng.stats["prefill_chunks_behind_decode"] == 0
  assert eng.stats["admits_ahead"] == 0
  # both lanes live, nothing queued: the step, its read, nothing else
  assert _one_pass(eng, log) == ["step", "read step"]


def test_a_pass_with_live_lanes_dispatches_the_step_first(tiny):
  """The whole order, and both counters against a hand count. Horizon 4;
  A has 6 tokens to emit, B 9: after the admitting pass and one decode pass
  A has 1 left and B 4, so the third pass's step is certain to free both.
  ONE admission goes behind it (C into lane 0, chunks AND insert, nothing
  read), the step is read and harvested, C's first token is read, and the
  second free lane is admitted the old way."""
  eng, log = _engine(tiny), []
  _record(eng, log)
  a = _submit(eng, _prompt(5, 1), 6)
  b = _submit(eng, _prompt(7, 2), 9)
  eng._pass()
  eng._pass()
  assert (a.generated, b.generated) == (5, 5)
  c = _submit(eng, _prompt(9, 3), 7)
  d = _submit(eng, _prompt(4, 4), 5)
  assert _one_pass(eng, log) == [
      "step", "chunks 9", "insert 0", "read step", "read first",
      "chunks 4", "read first", "insert 1"]
  assert a.done.is_set() and b.done.is_set()
  assert eng._slots == [c, d] and eng._admitting is None
  st = eng.stats
  assert (st["prefills"], st["prefill_chunks"]) == (4, 4)
  assert st["prefill_chunks_behind_decode"] == 1 and st["admits_ahead"] == 1
  _run_out(eng, [c, d])
  _assert_parity(tiny, eng, [a, b, c, d])
  assert st["slab_in_place"] == st["slab_dispatches"] > 0


def test_a_lane_taken_ahead_gets_its_successors_row_in_the_same_pass(tiny):
  """The successor's row is in the lane when the pass ends, its first token
  is the lane's last token, and the next pass's step continues from it: the
  first decode token follows the first token."""
  cfg, state = tiny
  eng = _engine(tiny, num_slots=1)
  a = _submit(eng, _prompt(5, 5), 4)
  eng._pass()                           # a admitted: 1 of 4 emitted
  c = _submit(eng, _prompt(6, 6), 8)
  eng._pass()                           # 3 left <= horizon: c goes ahead
  assert a.done.is_set() and eng.stats["admits_ahead"] == 1
  want = _reference(state.params, cfg, c.prompt, 8)[len(c.prompt):]
  assert eng._slots == [c] and c.tokens == [want[0]]
  assert eng._last[0] == want[0]
  eng._pass()
  assert c.tokens == list(want[:5])
  _run_out(eng, [c])
  _assert_parity(tiny, eng, [a, c])


def test_a_lane_that_ends_on_eos_is_never_taken_ahead(tiny):
  """EOS cannot be known ahead: a lane whose budget does not end inside the
  horizon keeps its pass as it was, though it stops on EOS inside it. Its
  successor is admitted after the harvest, dispatch, read, insert."""
  cfg, state = tiny
  p = _prompt(5, 7)
  gen = _reference(state.params, cfg, p, 8)[len(p):]
  eos = int(gen[2])                     # the third token ends the request
  assert eos != PAD and eos not in gen[:2]
  eng, log = _engine(tiny, num_slots=1, eos_id=eos), []
  _record(eng, log)
  a = _submit(eng, p, 30)
  eng._pass()
  c = _submit(eng, _prompt(6, 8), 3)
  assert _one_pass(eng, log) == ["step", "read step", "chunks 6",
                                 "read first", "insert 0"]
  assert a.done.is_set() and a.generated == 3 and eng._slots == [c]
  assert eng.stats["admits_ahead"] == 0
  assert eng.stats["prefill_chunks_behind_decode"] == 0
  _run_out(eng, [c])
  _assert_parity(tiny, eng, [a, c], eos_id=eos)


def test_a_budget_that_ends_in_the_horizon_certifies_the_lane_under_eos(tiny):
  """With an EOS id set the budget still bounds the lane: ``remaining <=
  horizon`` ends it inside the scan whatever EOS does."""
  eng = _engine(tiny, num_slots=1, eos_id=63)
  a = _submit(eng, _prompt(5, 9), 5)
  eng._pass()
  c = _submit(eng, _prompt(6, 10), 6)
  eng._pass()
  assert a.done.is_set() and eng.stats["admits_ahead"] == 1
  _run_out(eng, [c])
  _assert_parity(tiny, eng, [a, c], eos_id=63)


def test_a_one_token_request_admitted_ahead_leaves_the_lane_free(tiny):
  """Its row was inserted before its only token was read: nobody reads the
  row, the lane is free after the pass, and the next request's insert
  overwrites it."""
  eng, log = _engine(tiny, num_slots=1), []
  _record(eng, log)
  a = _submit(eng, _prompt(5, 11), 4)
  eng._pass()
  x = _submit(eng, _prompt(8, 12), 1)
  assert _one_pass(eng, log) == ["step", "chunks 8", "insert 0",
                                 "read step", "read first"]
  assert a.done.is_set() and x.done.is_set()
  assert eng._slots == [None] and eng._admitting is None
  assert eng.stats["admits_ahead"] == 1 and eng.stats["completed"] == 2
  y = _submit(eng, _prompt(3, 13), 6)
  _run_out(eng, [y])
  _assert_parity(tiny, eng, [a, x, y])


# -- faults, cancellation and deadlines with an admission in flight -----------


@pytest.fixture
def fresh_chaos(monkeypatch):
  chaos.reset()
  yield monkeypatch
  monkeypatch.delenv(chaos.ENV_SERVE, raising=False)
  chaos.reset()


def _two_lanes_about_to_end(eng):
  a = _submit(eng, _prompt(5, 1), 6)
  b = _submit(eng, _prompt(7, 2), 9)
  eng._pass()
  eng._pass()
  return a, b


def test_a_decode_fault_replays_bit_identically(tiny, fresh_chaos):
  """``decode#2:raise``: the second step's dispatch raises, before anything
  is queued behind it. Nobody was being admitted: every lane is blamed."""
  fresh_chaos.setenv(chaos.ENV_SERVE, "decode#2:raise")
  chaos.reset()
  eng = _engine(tiny, restart_backoff=0.0, poison_crashes=3)
  a, b = _two_lanes_about_to_end(eng)
  c = _submit(eng, _prompt(9, 3), 7)
  _run_out(eng, [a, b, c], recover=True)
  assert eng.stats["engine_restarts"] == 1 and eng.stats["replays"] == 2
  assert (a.crash_count, b.crash_count, c.crash_count) == (1, 1, 0)
  _assert_parity(tiny, eng, [a, b, c])


def test_a_prefill_fault_behind_a_step_replays_bit_identically(
    tiny, fresh_chaos):
  """The request of 9 tokens is admitted behind the second step and its
  prefill raises with that step unread: the lanes and the admission replay,
  the admission alone is blamed, and everyone's tokens are what they are."""
  fresh_chaos.setenv(chaos.ENV_SERVE, "prefill@9#1:raise")
  chaos.reset()
  eng, log = _engine(tiny, restart_backoff=0.0, poison_crashes=3), []
  _record(eng, log)
  a, b = _two_lanes_about_to_end(eng)
  c = _submit(eng, _prompt(9, 3), 7)
  del log[:]
  with pytest.raises(RuntimeError, match="chaos") as crash:
    eng._pass()
  assert log == ["step"] and eng._admitting is c
  assert eng._recover(crash.value)
  _run_out(eng, [a, b, c])
  assert eng.stats["engine_restarts"] == 1 and eng.stats["replays"] == 3
  assert (a.crash_count, b.crash_count, c.crash_count) == (0, 0, 1)
  _assert_parity(tiny, eng, [a, b, c])


def test_a_fault_in_the_steps_read_is_nobodys_and_loses_no_admission(tiny):
  """The step's read fails with an admission in flight behind it: the
  admission is a victim like the lanes (it is in no lane and in no queue),
  and the fault is blamed on all of them, not on the admission alone."""
  eng, log = _engine(tiny, restart_backoff=0.0, poison_crashes=3), []

  def fail():
    hook[0] = None
    raise RuntimeError("the device failed under the step")

  hook = _record(eng, log)
  a, b = _two_lanes_about_to_end(eng)
  c = _submit(eng, _prompt(9, 3), 7)
  hook[0] = fail
  with pytest.raises(RuntimeError, match="device failed") as crash:
    eng._pass()
  assert eng._admitting is c and eng._blame is None
  assert eng._recover(crash.value)
  _run_out(eng, [a, b, c])
  assert eng.stats["replays"] == 3
  assert (a.crash_count, b.crash_count, c.crash_count) == (1, 1, 1)
  _assert_parity(tiny, eng, [a, b, c])


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_cancel_and_deadline_reach_a_request_in_flight_behind_a_step(
    tiny, how):
  """Cancelled (or expired) between its dispatches and its first token's
  read, the request takes its lane like any admitted one and the next
  pass's reap frees the lane; its successor's tokens are its own."""
  eng, log = _engine(tiny, num_slots=1), []

  def strike():
    assert eng._admitting is c
    if how == "cancel":
      c.cancelled.set()
    else:
      c.deadline = time.monotonic() - 1.0

  hook = _record(eng, log)
  a = _submit(eng, _prompt(5, 5), 4)
  eng._pass()
  c = _submit(eng, _prompt(6, 6), 8)
  d = _submit(eng, _prompt(7, 7), 5)
  hook[0] = strike
  eng._pass()
  hook[0] = None
  assert eng._slots == [c] and c.generated == 1 and not c.done.is_set()
  assert _one_pass(eng, log) == ["chunks 7", "read first", "insert 0"]
  assert c.done.is_set() and eng._slots == [d]
  assert isinstance(c.error, RequestCancelled if how == "cancel"
                    else DeadlineExceeded)
  assert eng.stats["cancelled" if how == "cancel" else "expired"] == 1
  _run_out(eng, [d])
  _assert_parity(tiny, eng, [a, d])


# -- the threaded loop --------------------------------------------------------


def test_a_closed_loop_of_mixed_lengths_keeps_every_requests_tokens(tiny):
  """Request by request against the single-request decode, with lanes taken
  ahead all through the run (the phase counters of such a run:
  tests/test_serve_phases.py)."""
  cfg, state = tiny
  rng = np.random.RandomState(17)
  # few distinct shapes: the oracle compiles one program a (length, budget)
  prompts = [rng.randint(1, 64, (int(n),)).astype(np.int32)
             for n in rng.choice([2, 5, 11, 17, 29], 40)]
  budgets = [int(x) for x in rng.choice([1, 3, 6, 10, 15], 40)]
  with ServingEngine(state.params, cfg, num_slots=3, eos_id=None,
                     horizon=4) as eng:
    rids = [eng.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, budgets)]
    outs = [eng.result(rid, timeout=120) for rid in rids]
  st = eng.stats                        # the loop has stopped
  for p, n, out in zip(prompts, budgets, outs):
    np.testing.assert_array_equal(out, _reference(state.params, cfg, p, n))
  assert st["replay_mismatches"] == 0 and st["engine_restarts"] == 0
  assert st["prefills"] == st["completed"] == 40
  assert 0 < st["admits_ahead"] <= st["prefill_chunks_behind_decode"] \
      <= st["prefill_chunks"]
  assert st["slab_in_place"] == st["slab_dispatches"] > 0
  for t_key, e_key in zip(PHASE_KEYS, EMPTY_KEYS):
    assert 0.0 <= st[e_key] <= st[t_key] + 1e-9, t_key


# -- the paged pool and speculation -------------------------------------------


def test_the_paged_pool_takes_the_order_but_a_free_lane_only(tiny):
  """Pages are released and page tables reset in the harvest: the paged
  engine queues the chunks behind the step, into a lane that is free
  already, and inserts after its read. A lane about to end is not taken."""
  eng, log = _engine(tiny, page_size=4, prefix_pages=6), []
  _record(eng, log)
  shared = _prompt(8, 21)
  a = _submit(eng, np.concatenate([shared, _prompt(3, 22)]), 6)
  eng._pass()
  b = _submit(eng, np.concatenate([shared, _prompt(5, 23)]), 9)
  assert _one_pass(eng, log) == ["step", "chunks 13", "read step",
                                 "read first", "insert 1"]
  assert eng._slots == [a, b] and eng.stats["prefix_hits"] == 1
  assert eng.stats["prefill_chunks_behind_decode"] \
      == eng.stats["prefill_chunks"] - 1 > 0
  # a ends inside the next step; the paged engine does not take it ahead
  c = _submit(eng, np.concatenate([shared, _prompt(2, 24)]), 5)
  assert a.max_new_tokens - a.generated <= eng.horizon
  assert _one_pass(eng, log) == ["step", "read step", "chunks 10",
                                 "read first", "insert 0"]
  assert a.done.is_set() and eng._slots == [c, b]
  assert eng.stats["admits_ahead"] == 0
  _run_out(eng, [b, c])
  _assert_parity(tiny, eng, [a, b, c])
  assert eng.kv_pages_in_use == eng._prefix.pages_held


def test_speculation_takes_a_lane_ahead_where_its_budget_certifies(tiny):
  """Every round emits at least one token a live lane, so a budget of at
  most ``rounds`` ends inside the dispatch; a larger one may not."""
  eng, log = _engine(tiny, num_slots=1, spec_depth=2, spec_layers=1), []
  _record(eng, log)
  rounds = eng._spec_rounds
  assert rounds == 2
  a = _submit(eng, _prompt(5, 31), 1 + rounds + 1)
  eng._pass()                           # a admitted: rounds + 1 left
  c = _submit(eng, _prompt(6, 32), 7)
  first = _one_pass(eng, log)
  if not a.done.is_set():
    # one more than the rounds certify: the dispatch was not queued behind
    assert first[:2] == ["step", "read step"]
    assert eng.stats["admits_ahead"] == 0
  left = a.max_new_tokens - a.generated
  while not a.done.is_set():
    assert eng._slots == [a]
    took = _one_pass(eng, log)
    if left <= rounds:
      assert took == ["step", "chunks 6", "insert 0", "read step",
                      "read first"]
    left = a.max_new_tokens - a.generated
  assert eng.stats["admits_ahead"] in (0, 1)
  _run_out(eng, [c])
  _assert_parity(tiny, eng, [a, c])


def test_the_speculative_paged_stack_keeps_parity_under_the_order(tiny):
  cfg, state = tiny
  rng = np.random.RandomState(41)
  prefix = rng.randint(1, 64, (12,)).astype(np.int32)
  prompts = [np.concatenate([prefix,
                             rng.randint(1, 64, (n,)).astype(np.int32)])
             for n in (3, 5, 4, 6, 2, 3, 7, 1)]
  for kw in (dict(spec_depth=2, spec_layers=1),
             dict(page_size=4, prefix_pages=6, spec_depth=2)):
    with ServingEngine(state.params, cfg, num_slots=2, eos_id=None,
                       horizon=4, **kw) as eng:
      outs = eng.generate(prompts, max_new_tokens=8, timeout=120)
      st = dict(eng.stats)
    for p, out in zip(prompts, outs):
      np.testing.assert_array_equal(out,
                                    _reference(state.params, cfg, p, 8))
    assert st["replay_mismatches"] == 0
    assert st["slab_in_place"] == st["slab_dispatches"] > 0
    assert (st["admits_ahead"] > 0) == ("page_size" not in kw)
