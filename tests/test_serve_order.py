"""The order of a serving pass (``ServingEngine._pass``): with a live lane the
decode step is dispatched FIRST, before the step in flight has been read (its
lane state carried on the device), one admission's chunks and insert are
queued behind it unread, the OLDER step's tokens are read and harvested, and
only then the first token of the admission that was waiting — CPU, toy width,
a recording decoder, no clock. The paged pool and speculation keep one step
at a time: dispatch, queue one admission, read.

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

  chunks, first, merge = dec.prefill_chunks, dec.prefill_first, \
      dec.merge_lanes

  def merge_lanes(toks, *a, **kw):
    # the unread step's tokens go in as they are, on the device: no read
    log.append("carry")
    return merge(toks._array, *a, **kw)

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
  dec.merge_lanes = merge_lanes
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


def _unread(eng):
  return [adm.req for adm in eng._unread]


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
  # both lanes live, nothing queued, no step in flight: the first step of a
  # run goes from the host's arrays as it always did, and is left unread
  assert _one_pass(eng, log) == ["step"]
  assert eng._flight is not None and eng.stats["decode_dispatches_ahead"] == 0
  # the next is dispatched from ITS outputs before it is read
  assert _one_pass(eng, log) == ["carry", "step", "read step"]
  assert (a.generated, b.generated) == (5, 5)


def test_a_pass_with_live_lanes_dispatches_the_step_first(tiny):
  """The whole order where a run of steps ENDS, and both counters against a
  hand count. Horizon 4; A has 6 tokens to emit, B 9: after the admitting
  pass and two decode passes A has 1 left and B 4 with the second step in
  flight, which is certain to free both, so no lane can be live in a third
  and none is dispatched. ONE admission goes behind the step in flight (C
  into lane 0, chunks AND insert, nothing read), that step is read and
  harvested, C's first token is read (nothing is left in flight to run ahead
  of), and the second free lane is admitted the old way."""
  eng, log = _engine(tiny), []
  _record(eng, log)
  a = _submit(eng, _prompt(5, 1), 6)
  b = _submit(eng, _prompt(7, 2), 9)
  eng._pass()
  eng._pass()
  eng._pass()
  assert (a.generated, b.generated) == (5, 5) and eng._flight is not None
  c = _submit(eng, _prompt(9, 3), 7)
  d = _submit(eng, _prompt(4, 4), 5)
  assert _one_pass(eng, log) == [
      "chunks 9", "insert 0", "read step", "read first",
      "chunks 4", "read first", "insert 1"]
  assert a.done.is_set() and b.done.is_set() and eng._flight is None
  assert eng._slots == [c, d] and eng._admitting is None
  st = eng.stats
  assert (st["prefills"], st["prefill_chunks"]) == (4, 4)
  assert st["prefill_chunks_behind_decode"] == 1 and st["admits_ahead"] == 1
  _run_out(eng, [c, d])
  _assert_parity(tiny, eng, [a, b, c, d])
  assert st["slab_in_place"] == st["slab_dispatches"] > 0


def test_a_pass_with_a_step_in_flight_dispatches_the_next_before_it_reads(
    tiny):
  """The whole order in the middle of a run. B decodes for a long time; A has
  6 tokens to emit, C 7. With step 1 in flight the pass dispatches step 2
  from its outputs, queues C behind it into the lane A's budget certifies
  (the host counts what the unread step spends), and only then reads step 1.
  The next pass dispatches step 3 with C's lane live from the prefill's own
  output, reads step 2 (which frees the lane), and then C's first token; the
  pass after it takes C's lane ahead for D."""
  eng, log = _engine(tiny), []
  _record(eng, log)
  a = _submit(eng, _prompt(5, 1), 6)
  b = _submit(eng, _prompt(7, 2), 30)
  eng._pass()
  assert _one_pass(eng, log) == ["step"]
  c = _submit(eng, _prompt(9, 3), 7)
  d = _submit(eng, _prompt(4, 4), 5)
  assert _one_pass(eng, log) == ["carry", "step", "chunks 9", "insert 0",
                                 "read step"]
  assert (a.generated, b.generated) == (5, 5)
  assert eng._slots == [a, b] and _unread(eng) == [c]
  assert eng._admitting is None and eng.queue_depth == 2     # C and D
  assert _one_pass(eng, log) == ["carry", "step", "read step", "read first"]
  assert a.done.is_set() and eng._slots == [c, b] and _unread(eng) == []
  assert (c.generated, b.generated) == (1, 9)
  assert _one_pass(eng, log) == ["carry", "step", "chunks 4", "insert 0",
                                 "read step"]
  assert (c.generated, b.generated) == (5, 13) and _unread(eng) == [d]
  st = eng.stats
  assert st["admits_ahead"] == 2 and st["prefill_chunks_behind_decode"] == 2
  assert (st["decode_dispatches"], st["decode_dispatches_ahead"]) == (4, 3)
  _run_out(eng, [a, b, c, d])
  _assert_parity(tiny, eng, [a, b, c, d])
  assert st["slab_in_place"] == st["slab_dispatches"] > 0
  assert st["decode_dispatches"] * eng.horizon == st["steps"]


def test_a_lane_taken_ahead_gets_its_successors_row_in_the_same_pass(tiny):
  """The successor's row is queued into the lane in the pass that dispatches
  the step which frees it. The next pass's step continues from the first
  token ON THE DEVICE, before the host has read it; the host reads it after
  the older step's harvest, and the first decode token follows it."""
  cfg, state = tiny
  eng, log = _engine(tiny, num_slots=1), []
  _record(eng, log)
  a = _submit(eng, _prompt(5, 5), 4)
  eng._pass()                           # a admitted: 1 of 4 emitted
  c = _submit(eng, _prompt(6, 6), 8)
  # 3 left <= horizon: c goes ahead, behind the step
  assert _one_pass(eng, log) == ["step", "chunks 6", "insert 0"]
  assert eng.stats["admits_ahead"] == 1 and _unread(eng) == [c]
  want = _reference(state.params, cfg, c.prompt, 8)[len(c.prompt):]
  assert _one_pass(eng, log) == ["carry", "step", "read step", "read first"]
  assert a.done.is_set() and eng._slots == [c] and c.tokens == [want[0]]
  assert eng._last[0] == want[0]
  eng._pass()
  assert c.tokens == list(want[:5])
  _run_out(eng, [c])
  _assert_parity(tiny, eng, [a, c])


def test_a_lane_that_ends_on_eos_is_never_taken_ahead(tiny):
  """EOS cannot be known ahead: a lane whose budget does not end inside the
  horizon is not taken ahead, though it stops on EOS inside the step in
  flight. The step dispatched ahead of that read is frozen for the lane by
  the device's own carried mask and emits nothing; the successor is admitted
  after the harvest (dispatch, read, insert: behind that step, one dispatch
  later than a step read at once would refill the lane)."""
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
  assert _one_pass(eng, log) == ["step"]
  assert _one_pass(eng, log) == ["carry", "step", "read step", "chunks 6",
                                 "read first", "insert 0"]
  assert a.done.is_set() and a.generated == 3 and eng._slots == [c]
  assert eng.stats["admits_ahead"] == 0
  # the step in flight is the one A no longer decodes in: harvested for A's
  # lane, it adds nothing to anybody
  assert _one_pass(eng, log) == ["carry", "step", "read step"]
  assert a.generated == 3 and c.generated == 1
  _run_out(eng, [c])
  _assert_parity(tiny, eng, [a, c], eos_id=eos)


def test_a_lane_that_ends_on_eos_in_the_step_in_flight_emits_nothing_after(
    tiny):
  """Two lanes. A ends on EOS in step 1 while step 2, dispatched before that
  read, is in flight with B going on: the device's own carried mask holds A's
  lane off in step 2 (pad tokens, nothing the host asked for), its harvest
  gives A's lane to nobody, and the lane's next request, seated behind step 2
  by the host, goes live in step 3 by the host's override."""
  cfg, state = tiny
  p = _prompt(5, 7)
  gen = _reference(state.params, cfg, p, 8)[len(p):]
  eos = int(gen[2])
  eng, log = _engine(tiny, eos_id=eos), []
  _record(eng, log)
  a = _submit(eng, p, 30)
  b = _submit(eng, _prompt(7, 2), 30)
  eng._pass()
  eng._pass()
  c = _submit(eng, _prompt(6, 8), 9)
  assert _one_pass(eng, log) == ["carry", "step", "read step", "chunks 6",
                                 "read first", "insert 0"]
  assert a.done.is_set() and a.generated == 3 and eng._slots == [c, b]
  assert eng.stats["prefill_chunks_behind_decode"] == 1   # behind step 2
  in_flight = np.asarray(eng._flight.out[1]._array)       # step 2's tokens
  assert (in_flight[:, 0] == PAD).all() and (in_flight[:, 1] != PAD).all()
  before = b.generated
  assert _one_pass(eng, log) == ["carry", "step", "read step"]
  assert (a.generated, c.generated, b.generated) == (3, 1, before + 4)
  eng._pass()
  assert c.generated == 5               # live in step 3, not in step 2
  _run_out(eng, [b, c])
  _assert_parity(tiny, eng, [a, b, c], eos_id=eos)


def test_a_budget_that_ends_in_the_horizon_certifies_the_lane_under_eos(tiny):
  """With an EOS id set the budget still bounds the lane: ``remaining <=
  horizon`` ends it inside the scan whatever EOS does."""
  eng = _engine(tiny, num_slots=1, eos_id=63)
  a = _submit(eng, _prompt(5, 9), 5)
  eng._pass()
  c = _submit(eng, _prompt(6, 10), 6)
  eng._pass()
  assert eng.stats["admits_ahead"] == 1 and _unread(eng) == [c]
  eng._pass()
  assert a.done.is_set() and eng._slots == [c]
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
  assert _one_pass(eng, log) == ["step", "chunks 8", "insert 0"]
  # no lane can be live in a second step (X ends at its first token): none
  # is dispatched, the step in flight is read, then X's only token
  assert _one_pass(eng, log) == ["read step", "read first"]
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
  """Two steps dispatched, the second in flight unread; the THIRD is the one
  both lanes' budgets end in (1 and 4 tokens as it is given them)."""
  a = _submit(eng, _prompt(5, 1), 10)
  b = _submit(eng, _prompt(7, 2), 13)
  eng._pass()
  eng._pass()
  eng._pass()
  assert (a.generated, b.generated) == (5, 5) and eng._flight is not None
  return a, b


def test_a_decode_fault_replays_bit_identically(tiny, fresh_chaos):
  """``decode#3:raise``: the third step's dispatch raises with the second in
  flight unread, before anything is queued behind it: both go, and the lanes
  replay from what they had emitted. Nobody was being admitted: every lane is
  blamed."""
  fresh_chaos.setenv(chaos.ENV_SERVE, "decode#3:raise")
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
  """The request of 9 tokens is admitted behind the third step and its
  prefill raises with that step and the one before it unread: the lanes and
  the admission replay, the admission alone is blamed, and everyone's tokens
  are what they are."""
  fresh_chaos.setenv(chaos.ENV_SERVE, "prefill@9#1:raise")
  chaos.reset()
  eng, log = _engine(tiny, restart_backoff=0.0, poison_crashes=3), []
  _record(eng, log)
  a, b = _two_lanes_about_to_end(eng)
  c = _submit(eng, _prompt(9, 3), 7)
  del log[:]
  with pytest.raises(RuntimeError, match="chaos") as crash:
    eng._pass()
  assert log == ["carry", "step"] and eng._admitting is c
  assert eng._recover(crash.value)
  assert eng._flight is None
  _run_out(eng, [a, b, c])
  assert eng.stats["engine_restarts"] == 1 and eng.stats["replays"] == 3
  assert (a.crash_count, b.crash_count, c.crash_count) == (0, 0, 1)
  _assert_parity(tiny, eng, [a, b, c])


def test_a_fault_in_the_steps_read_is_nobodys_and_loses_no_admission(tiny):
  """The read of step 2 fails with step 3 queued and an admission in flight
  behind that: both steps go, the admission is a victim like the lanes (it
  is in no lane and in no queue), and the fault is blamed on all of them,
  not on the admission alone."""
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
  assert log[-4:] == ["step", "chunks 9", "insert 0", "read step"]
  assert _unread(eng) == [c] and eng._admitting is None
  assert eng._blame is None
  assert eng._recover(crash.value)
  assert eng._flight is None and _unread(eng) == []
  _run_out(eng, [a, b, c])
  assert eng.stats["replays"] == 3
  assert (a.crash_count, b.crash_count, c.crash_count) == (1, 1, 1)
  _assert_parity(tiny, eng, [a, b, c])


def test_a_decode_fault_with_a_lane_live_from_an_unread_first_token(
    tiny, fresh_chaos):
  """``decode#2:raise``: the second step's dispatch raises after its lane
  state was made on the device from step 1, unread, and from the first token
  of an admission nobody has read. The lane's old request (1 token emitted,
  3 in the step that goes) and the admission (nothing emitted) both replay."""
  fresh_chaos.setenv(chaos.ENV_SERVE, "decode#2:raise")
  chaos.reset()
  eng, log = _engine(tiny, num_slots=1, restart_backoff=0.0,
                     poison_crashes=3), []
  _record(eng, log)
  a = _submit(eng, _prompt(5, 5), 4)
  eng._pass()
  c = _submit(eng, _prompt(6, 6), 8)
  assert _one_pass(eng, log) == ["step", "chunks 6", "insert 0"]
  del log[:]
  with pytest.raises(RuntimeError, match="chaos") as crash:
    eng._pass()
  assert log == ["carry"] and _unread(eng) == [c] and a.generated == 1
  assert eng._recover(crash.value)
  assert eng._flight is None and _unread(eng) == []
  _run_out(eng, [a, c])
  st = eng.stats
  assert st["engine_restarts"] == 1 and st["replays"] == 2
  assert (a.crash_count, c.crash_count) == (1, 1)
  # the step before the fault is not in this log; the carry whose step
  # raised counts for nothing
  assert (st["decode_dispatches"], st["decode_dispatches_ahead"]) \
      == (1 + log.count("step"), log.count("carry") - 1)
  _assert_parity(tiny, eng, [a, c])


def test_a_read_fault_with_two_admissions_unread_loses_neither(tiny):
  """From a step's dispatch to the seat of the older one TWO admissions are
  unread: C, whose lane is live in step 2 from its prefill's output, and D,
  just queued behind step 2. The read of step 1 fails there: both steps go,
  and A, B, C and D all replay."""
  eng, log = _engine(tiny, restart_backoff=0.0, poison_crashes=3), []

  def fail():
    hook[0] = None
    raise RuntimeError("the device failed under the step")

  hook = _record(eng, log)
  a = _submit(eng, _prompt(5, 1), 4)
  b = _submit(eng, _prompt(7, 2), 9)
  eng._pass()
  c = _submit(eng, _prompt(9, 3), 7)
  d = _submit(eng, _prompt(4, 4), 5)
  assert _one_pass(eng, log) == ["step", "chunks 9", "insert 0"]
  hook[0] = fail
  del log[:]
  with pytest.raises(RuntimeError, match="device failed") as crash:
    eng._pass()
  assert log == ["carry", "step", "chunks 4", "insert 1", "read step"]
  assert _unread(eng) == [c, d] and eng._admitting is None
  assert eng.queue_depth == 2 and eng._blame is None
  assert eng._recover(crash.value)
  _run_out(eng, [a, b, c, d])
  assert eng.stats["replays"] == 4
  assert [r.crash_count for r in (a, b, c, d)] == [1, 1, 1, 1]
  _assert_parity(tiny, eng, [a, b, c, d])


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_cancel_and_deadline_reach_a_request_in_flight_behind_a_step(
    tiny, how):
  """Cancelled (or expired) between its dispatches and its first token's
  read, with its lane already live in the step dispatched ahead: the request
  takes its lane like any admitted one, the next pass's reap frees the lane,
  the tokens it has in the step in flight are discarded at the harvest, and
  its successor's tokens are its own."""
  eng, log = _engine(tiny, num_slots=1), []

  def strike():
    assert _unread(eng) == [c]
    if how == "cancel":
      c.cancelled.set()
    else:
      c.deadline = time.monotonic() - 1.0

  hook = _record(eng, log)
  a = _submit(eng, _prompt(5, 5), 4)
  eng._pass()
  c = _submit(eng, _prompt(6, 6), 8)
  d = _submit(eng, _prompt(7, 7), 5)
  eng._pass()                           # the step, c behind it, nothing read
  hook[0] = strike
  eng._pass()                           # c's lane live in the next; the read
  hook[0] = None
  assert eng._slots == [c] and c.generated == 1 and not c.done.is_set()
  # the reap frees the lane: nothing can be live, d goes behind the step in
  # flight, whose tokens for c's lane are nobody's
  assert _one_pass(eng, log) == ["chunks 7", "insert 0", "read step",
                                 "read first"]
  assert c.done.is_set() and c.generated == 1 and eng._slots == [d]
  assert isinstance(c.error, RequestCancelled if how == "cancel"
                    else DeadlineExceeded)
  assert eng.stats["cancelled" if how == "cancel" else "expired"] == 1
  _run_out(eng, [d])
  _assert_parity(tiny, eng, [a, d])


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_cancel_and_deadline_reach_a_request_whose_step_is_in_flight(
    tiny, how):
  """C decodes in lane 1 and is cancelled (or expires) with a step in flight
  it is live in. The reap frees the lane; the NEXT dispatch switches it off
  by the host's override (the device would have carried it on), D's row goes
  into it behind that step, and the harvest of the step in flight discards
  C's tokens. D's tokens are its own, A's never notice."""
  cfg, state = tiny
  eng, log = _engine(tiny), []
  _record(eng, log)
  a = _submit(eng, _prompt(5, 1), 30)
  c = _submit(eng, _prompt(6, 6), 20)
  for _ in range(3):
    eng._pass()
  assert c.generated == 5 and eng._flight.reqs == [a, c]
  if how == "cancel":
    c.cancelled.set()
  else:
    c.deadline = time.monotonic() - 1.0
  d = _submit(eng, _prompt(7, 7), 6)
  assert _one_pass(eng, log) == ["carry", "step", "chunks 7", "insert 1",
                                 "read step"]
  assert c.done.is_set() and c.generated == 5 and a.generated == 9
  assert isinstance(c.error, RequestCancelled if how == "cancel"
                    else DeadlineExceeded)
  in_flight = np.asarray(eng._flight.out[1]._array)   # the step after the reap
  assert (in_flight[:, 1] == PAD).all() and (in_flight[:, 0] != PAD).all()
  assert eng._slots == [a, None] and _unread(eng) == [d]
  assert _one_pass(eng, log) == ["carry", "step", "read step", "read first"]
  assert eng._slots == [a, d] and eng.stats["admits_ahead"] == 0
  want = _reference(state.params, cfg, c.prompt, 20)[len(c.prompt):]
  assert c.tokens == list(want[:5])
  _run_out(eng, [a, d])
  _assert_parity(tiny, eng, [a, d])


def test_decode_dispatches_ahead_counts_what_the_log_shows(tiny):
  """A closed loop driven pass by pass: every step but the first of a run is
  dispatched from the lane state the device carried (one ``carry`` each, made
  before the older step's read), every dispatched step is harvested, and the
  one counter says how many went ahead."""
  eng, log = _engine(tiny, num_slots=3), []
  _record(eng, log)
  rng = np.random.RandomState(23)
  reqs = [_submit(eng, _prompt(int(n), 40 + i), int(m))
          for i, (n, m) in enumerate(zip(rng.choice([2, 5, 11, 17], 12),
                                         rng.choice([1, 3, 6, 10, 15], 12)))]
  _run_out(eng, reqs)
  assert eng._flight is None and _unread(eng) == []
  st = eng.stats
  steps, carries = log.count("step"), log.count("carry")
  assert (st["decode_dispatches"], st["decode_dispatches_ahead"]) \
      == (steps, carries)
  assert 0 < carries < steps == log.count("read step")
  # a carry is followed at once by its step, and a step in flight is read
  # only after the next one has been dispatched
  for i, event in enumerate(log):
    if event == "carry":
      assert log[i + 1] == "step"
  unread = 0
  for event in log:
    unread += (event == "step") - (event == "read step")
    assert 0 <= unread <= 2
  assert st["steps"] == steps * eng.horizon
  assert st["admits_ahead"] > 0
  _assert_parity(tiny, eng, reqs)


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


@pytest.mark.parametrize("with_eos", [False, True])
def test_a_closed_loop_with_a_step_always_in_flight_keeps_every_token(
    tiny, with_eos):
  """Mixed lengths, requests of ONE token among them, more requests than
  lanes: request by request against the single-request decode, without an
  EOS id and with one that ends requests inside steps in flight."""
  cfg, state = tiny
  rng = np.random.RandomState(29)
  prompts = [rng.randint(1, 64, (int(n),)).astype(np.int32)
             for n in rng.choice([2, 5, 11, 17], 24)]
  budgets = [int(x) for x in rng.choice([1, 3, 6, 10, 15], 24)]
  eos = None
  if with_eos:
    # the token most of these requests emit somewhere past their first
    tails = [_reference(state.params, cfg, p, n)[len(p) + 1:]
             for p, n in zip(prompts, budgets)]
    eos = int(np.bincount(np.concatenate(tails)[np.concatenate(tails)
                                               != PAD]).argmax())
    assert sum(eos in t for t in tails) >= 3
  with ServingEngine(state.params, cfg, num_slots=3, eos_id=eos,
                     horizon=4) as eng:
    rids = [eng.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, budgets)]
    outs = [eng.result(rid, timeout=120) for rid in rids]
  st = eng.stats                        # the loop has stopped
  for p, n, out in zip(prompts, budgets, outs):
    np.testing.assert_array_equal(
        out, _reference(state.params, cfg, p, n, eos_id=eos))
  assert st["replay_mismatches"] == 0 and st["engine_restarts"] == 0
  assert st["prefills"] == st["completed"] == 24
  assert 0 < st["decode_dispatches_ahead"] < st["decode_dispatches"]
  assert st["slab_in_place"] == st["slab_dispatches"] > 0


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


@pytest.mark.parametrize("kw", [dict(page_size=4),
                                dict(spec_depth=2, spec_layers=1)],
                         ids=["paged", "speculative"])
def test_the_paged_pool_and_speculation_keep_one_step_at_a_time(tiny, kw):
  """Run-ahead depth 0: pages are released and page tables reset in the
  harvest, and speculation emits a data-dependent count a lane, so every
  step is read in the pass that dispatched it, from the host's arrays, and
  nothing is ever left in flight or unread between passes."""
  eng, log = _engine(tiny, **kw), []
  _record(eng, log)
  assert not eng._run_ahead
  reqs = [_submit(eng, _prompt(n, 50 + n), m)
          for n, m in ((5, 6), (7, 9), (9, 7), (4, 5), (3, 12))]
  for _ in range(200):
    if all(r.done.is_set() for r in reqs):
      break
    took = _one_pass(eng, log)
    assert "carry" not in took
    assert took.count("step") == took.count("read step") <= 1
    if "step" in took:
      assert took.index("step") < took.index("read step")
    assert eng._flight is None and _unread(eng) == []
  st = eng.stats
  assert st["decode_dispatches"] > 0 and st["decode_dispatches_ahead"] == 0
  _assert_parity(tiny, eng, reqs)
