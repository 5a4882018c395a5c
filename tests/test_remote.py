"""Cross-host serving plane tests (serving/host.py + serving/remote.py).

The load-bearing claim lifts the fleet suite's across a PROCESS
boundary: the SAME ``ServingFleet`` — load-aware dispatch, health
ejection, failover replay, zero-shed rolling swaps — routed over
``RemoteReplica`` proxies whose engines live in ``ServingHost``
runtimes behind the rendezvous wire (SHREG/SHSYNC/SHBYE) must produce
outputs bit-identical to single-request decodes, with stream positions
exactly-once even when the wire retries or the host dies mid-decode.

Tier-1 tests run hosts in THREAD mode (``run_host_thread``: real
sockets, framing and chunking — only the process boundary elided);
the chaos kill pin spawns real executor processes and is ``slow``
(covered by ``make fleet-chaos`` and ``make check``). Host faults are
driven deterministically via ``TOS_CHAOS_HOST`` (utils/chaos.py).
"""

import contextlib
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.control import rendezvous
from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.serving import (
    DeadlineExceeded, ModelRegistry, RequestCancelled, ServingFleet,
    ServingOverloaded)
from tensorflowonspark_tpu.serving import fleet as fleet_mod
from tensorflowonspark_tpu.serving import host as host_mod
from tensorflowonspark_tpu.serving import remote as remote_mod
from tensorflowonspark_tpu.serving import scheduler as sched
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


def _workload(seed, n=8, plens=(3, 5, 7), budgets=(4, 8)):
  rng = np.random.RandomState(seed)
  return [(rng.randint(1, 64, (int(rng.choice(plens)),)).astype(np.int32),
           int(rng.choice(budgets))) for _ in range(n)]


@contextlib.contextmanager
def _hosts_up(tiny_state, root, n=2, publish=1, serve_opts=None,
              plane_kw=None, host_kw=None, hosts_out=None):
  """A real rendezvous Server with the serving plane attached, a
  registry at ``root`` holding ``publish`` committed versions of the
  tiny model, and ``n`` thread-mode ServingHosts registered and
  syncing. Yields ``(addr, plane, versions)``; pass a list as
  ``hosts_out`` to also collect the in-process host objects (thread
  mode shares the process, so a test may reach through to the live
  engine — e.g. to gate decode progress deterministically)."""
  cfg, state = tiny_state
  opts = dict(num_slots=2, eos_id=EOS, pad_id=PAD, horizon=2)
  opts.update(serve_opts or {})
  reg = ModelRegistry(str(root))
  extra = {"model_cfg": host_mod.cfg_wire(cfg), "serve_opts": opts}
  versions = [reg.publish(state.params, step=100 * (i + 1), extra=extra)
              for i in range(publish)]
  server = rendezvous.Server(count=1)
  addr = server.start()
  plane = remote_mod.attach_serving_plane(server, **(plane_kw or {}))
  stops = []
  try:
    for hid in range(n):
      h, stop = host_mod.run_host_thread(addr, hid, registry_root=str(root),
                                         **(host_kw or {}))
      if hosts_out is not None:
        hosts_out.append(h)
      stops.append(stop)
    plane.await_hosts(n, timeout=60)
    yield addr, plane, versions
  finally:
    for stop in stops:
      stop()
    server.stop()


class TestRemoteFleet:
  def test_fleet_parity_and_stream_across_the_wire(self, tiny_state,
                                                   tmp_path):
    """The tentpole claim, fault-free: a ServingFleet routed over
    RemoteReplica proxies (engines registry-built in ServingHost
    runtimes behind real sockets) serves the mixed workload with every
    output bit-identical to its single-request decode, and a stream()
    consumer sees exactly the generated suffix, each position once."""
    cfg, state = tiny_state
    with _hosts_up(tiny_state, tmp_path, n=2) as (addr, plane, versions):
      fl = ServingFleet(
          remote_mod.remote_engine_factory(plane, version=versions[0]),
          num_replicas=2,
          health_probe=remote_mod.wire_health_probe(addr)).start()
      try:
        work = _workload(3, n=8)
        frids = [fl.submit(p, max_new_tokens=b) for p, b in work]
        # stream() consumes its request, so the result loop skips it
        streamed = list(fl.stream(frids[0], timeout=120))
        outs = [fl.result(fr, timeout=120) for fr in frids[1:]]
        stats = dict(fl.stats)
      finally:
        fl.stop()
      for (p, b), out in zip(work[1:], outs):
        np.testing.assert_array_equal(
            out, _reference(state.params, cfg, p, b))
      p0, b0 = work[0]
      ref0 = _reference(state.params, cfg, p0, b0)
      assert streamed == [int(t) for t in ref0[len(p0):]]
      assert stats["completed"] == len(work) and stats["shed"] == 0
      # both hosts took traffic and the wire actually chunked/synced
      assert plane.stats["syncs"] > 0 and plane.stats["bad_messages"] == 0

  def test_chunked_prompt_reassembles_across_frames(self, tiny_state,
                                                    tmp_path):
    """A prompt bigger than the negotiated chunk budget ships as staged
    parts and reassembles host-side in order — the >4MB-frame refusal
    never triggers because no single frame approaches it."""
    cfg, state = tiny_state
    with _hosts_up(tiny_state, tmp_path, n=1,
                   plane_kw={"chunk": 8}) as (addr, plane, versions):
      rep = remote_mod.RemoteReplica(plane, version=versions[0])
      rep.start()
      try:
        prompt = np.arange(1, 30, dtype=np.int32) % 60 + 1
        rid = rep.submit(prompt, max_new_tokens=6)
        out = rep.result(rid, timeout=120)
      finally:
        rep.stop()
      np.testing.assert_array_equal(
          out, _reference(state.params, cfg, prompt, 6))

  def test_overloaded_reconstructed_with_fields(self, tiny_state,
                                                tmp_path):
    """An admission rejection crosses the wire as a structured error
    and reaches the caller as a ServingOverloaded with the same
    backpressure fields the fleet's retry loop reads."""
    with _hosts_up(tiny_state, tmp_path, n=1,
                   serve_opts={"max_queue": 1}) as (addr, plane, versions):
      rep = remote_mod.RemoteReplica(plane, version=versions[0],
                                     admit_timeout=30.0)
      rep.start()
      try:
        work = _workload(11, n=6, budgets=(16,))
        rejection = None
        for p, b in work:
          try:
            rep.submit(p, max_new_tokens=b)
          except ServingOverloaded as e:
            rejection = e
            break
        assert rejection is not None
        assert rejection.queue_depth is not None
        assert rejection.retry_after is not None
        assert not rejection.draining
      finally:
        rep.stop()

  def test_deadline_and_cancel_cross_the_wire(self, tiny_state, tmp_path):
    """ttl re-anchors host-side (DeadlineExceeded comes back typed);
    cancel() relays over the wire and the stream ends in
    RequestCancelled."""
    hosts = []
    with _hosts_up(tiny_state, tmp_path, n=1,
                   serve_opts={"poll_interval": 0.005},
                   hosts_out=hosts) as (addr, plane, versions):
      rep = remote_mod.RemoteReplica(plane, version=versions[0])
      rep.start()
      eng = hosts[0].engine
      orig_decode = eng._decode_once
      try:
        # warm the jit caches so the ttl below times the decode, not XLA
        rep.result(rep.submit(np.asarray([3, 1, 4], np.int32),
                              max_new_tokens=4), timeout=120)
        rid = rep.submit(np.asarray([5, 9, 2], np.int32),
                         max_new_tokens=32, ttl=0.01)
        with pytest.raises(DeadlineExceeded):
          rep.result(rid, timeout=120)
        # the warm tiny model can finish a 32-token decode inside one
        # wire round-trip, so "cancel before it completes" cannot be a
        # timing bet: gate the (in-process, thread-mode) engine's decode
        # step until the relayed cancel is OBSERVED on the host's own
        # request handle, then release and let the reap fail it
        resume = threading.Event()
        eng._decode_once = lambda: (resume.wait(timeout=60)
                                    and orig_decode())
        rid2 = rep.submit(np.asarray([6, 5, 3], np.int32),
                          max_new_tokens=32)
        rep.request(rid2).cancelled.set()    # fires the wire relay
        deadline = time.monotonic() + 30
        while True:
          t = hosts[0]._track.get(rid2)
          if t is not None and t["handle"].cancelled.is_set():
            break
          assert time.monotonic() < deadline, \
              "cancel command never reached the host engine"
          time.sleep(0.01)
        resume.set()
        assert rep.cancel(rid2, timeout=60)  # idempotent; waits the reap
        with pytest.raises(RequestCancelled):
          rep.result(rid2, timeout=60)
      finally:
        eng._decode_once = orig_decode
        rep.stop()

  def test_rolling_swap_rebuilds_hosts_on_new_version(self, tiny_state,
                                                      tmp_path):
    """A rolling swap ACROSS the process seam: each drain frees its
    host, the replacement proxy rebuilds the commanded registry version
    on it (generation bumps host-side), outputs stay bit-identical and
    nothing sheds — deploy.py's canary/promote moves, cross-process."""
    cfg, state = tiny_state
    with _hosts_up(tiny_state, tmp_path, n=2,
                   publish=2) as (addr, plane, versions):
      v1, v2 = versions
      fl = ServingFleet(
          remote_mod.remote_engine_factory(plane, version=v1),
          num_replicas=2).start()
      try:
        for rid in fl.replica_states():
          fl.set_replica_version(rid, v1)
        work = _workload(7, n=6)
        frids = [fl.submit(p, max_new_tokens=b) for p, b in work]
        swap = fl.rolling_swap(
            timeout=120.0,
            engine_factory=remote_mod.remote_engine_factory(plane,
                                                            version=v2),
            version=v2)
        outs = [fl.result(fr, timeout=120) for fr in frids]
        stats = dict(fl.stats)
        served = set(fl.served_versions().values())
      finally:
        fl.stop()
      assert swap["swapped"] == 2
      assert all(r["drained"] for r in swap["replicas"])
      assert served == {v2}
      assert stats["shed"] == 0 and stats["replay_mismatches"] == 0
      for (p, b), out in zip(work, outs):
        np.testing.assert_array_equal(
            out, _reference(state.params, cfg, p, b))
      status = plane.status()
      assert all(row["generation"] == 2 and row["version"] == v2
                 for row in status.values())


class TestWireHealthProbe:
  def test_probe_rides_health_verb_and_keeps_local_path(self, tiny_state,
                                                        tmp_path):
    """The satellite pin against a real Server: wire_health_probe
    answers True for a syncing host (off the HEALTH reply's hosts row),
    False once that host departs, and falls back to ``engine.alive``
    for an engine with no host_id (the in-process path)."""
    with _hosts_up(tiny_state, tmp_path, n=1) as (addr, plane, versions):
      probe = remote_mod.wire_health_probe(addr)
      rep = remote_mod.RemoteReplica(plane, version=versions[0])
      rep.start()
      wrapped = fleet_mod.Replica(0, rep)
      assert probe(wrapped) is True
      # HEALTH itself carries the hosts enrichment
      client = rendezvous.Client(addr, timeout=5.0)
      try:
        reply = client._request({"type": "HEALTH"})
        assert "0" in (reply.get("hosts") or {})
      finally:
        client.close()
      rep.stop()

      class _Local:
        alive = True
      assert probe(fleet_mod.Replica(1, _Local())) is True
      _Local.alive = False
      assert probe(fleet_mod.Replica(1, _Local())) is False
    # server gone (context exited): host record departed -> probe False
    with _hosts_up(tiny_state, tmp_path, n=1) as (addr, plane, versions):
      probe = remote_mod.wire_health_probe(addr)
      rep = remote_mod.RemoteReplica(plane, version=versions[0])
      rep.start()
      wrapped = fleet_mod.Replica(0, rep)
      assert probe(wrapped) is True
      rep.kill(RuntimeError("probe pin"))
      deadline = time.monotonic() + 10
      while probe(wrapped) and time.monotonic() < deadline:
        time.sleep(0.05)
      assert probe(wrapped) is False


class TestPlaneWire:
  """Raw-verb coverage of the SHREG/SHSYNC/SHBYE dispatch arms against
  a real Server — the runtime counterpart of the TOS012 wire-verb
  contract (tools/analyze)."""

  def test_dispatch_arms_and_unregistered_resync(self):
    server = rendezvous.Server(count=1)
    addr = server.start()
    remote_mod.attach_serving_plane(server)
    client = rendezvous.Client(addr, timeout=5.0)
    try:
      reply = client._request({"type": "SHREG", "host_id": 5, "meta": {}})
      assert reply["type"] == "OK" and reply["chunk"] > 0
      reply = client._request({"type": "SHSYNC", "host_id": 5,
                               "events": [], "stats": {}})
      assert reply["type"] == "OK" and reply["cmds"] == []
      # an unknown host syncing gets the re-register nudge, not a crash
      reply = client._request({"type": "SHSYNC", "host_id": 77,
                               "events": [], "stats": {}})
      assert reply["type"] == "ERROR" and "unregistered" in reply["error"]
      reply = client._request({"type": "SHBYE", "host_id": 5})
      assert reply["type"] == "OK"
    finally:
      client.close()
      server.stop()

  def test_serving_verbs_error_without_plane(self):
    server = rendezvous.Server(count=1)
    addr = server.start()
    client = rendezvous.Client(addr, timeout=5.0)
    try:
      reply = client._request({"type": "SHREG", "host_id": 0, "meta": {}})
      assert reply["type"] == "ERROR"
      assert "no serving plane" in reply["error"]
    finally:
      client.close()
      server.stop()

  def test_token_events_apply_exactly_once(self):
    """Position-stamped deltas are idempotent (the host requeues
    unacked events after a failed sync) and a gap is a protocol bug
    that raises instead of corrupting the stream."""
    req = remote_mod.RemoteRequest(np.asarray([1], np.int32), 4, None,
                                   lambda: None)
    req._apply_tokens(0, [11, 12])
    req._apply_tokens(0, [11, 12, 13])      # resend + new suffix
    req._apply_tokens(3, [14])
    assert req.tokens == [11, 12, 13, 14]
    drained = []
    while not req.stream_q.empty():
      drained.append(req.stream_q.get_nowait())
    assert drained == [11, 12, 13, 14]      # each position exactly once
    with pytest.raises(RuntimeError):
      req._apply_tokens(9, [99])

  def test_error_codec_roundtrips_typed(self):
    over = sched.ServingOverloaded("busy", queue_depth=3, queued_tokens=40,
                                   retry_after=0.5, draining=True)
    back = remote_mod.decode_error(remote_mod.encode_error(over))
    assert isinstance(back, ServingOverloaded)
    assert (back.queue_depth, back.queued_tokens, back.retry_after,
            back.draining) == (3, 40, 0.5, True)
    for exc, typ in ((sched.DeadlineExceeded("late"), DeadlineExceeded),
                     (sched.RequestCancelled("bye"), RequestCancelled),
                     (sched.PoisonedRequest("bad"), sched.PoisonedRequest),
                     (ValueError("empty prompt"), ValueError),
                     (RuntimeError("boom"), RuntimeError)):
      back = remote_mod.decode_error(remote_mod.encode_error(exc))
      assert isinstance(back, typ)
    assert remote_mod.decode_error(None) is None


class _NeverSilent(remote_mod._HostRecord):
  """A host's record whose last sync is always now: the plane cannot read
  this host as silent, however late its syncs arrive."""
  __slots__ = ()
  last_sync = property(lambda self: time.monotonic(),
                       lambda self, t: None)


class TestHostChaos:
  """TOS_CHAOS_HOST-driven proofs (make fleet-chaos): host death and
  wire partitions injected deterministically at sync granularity.
  Chaos counters are per-process — every test resets them."""

  pytestmark = pytest.mark.chaos

  @pytest.fixture(autouse=True)
  def _fresh_chaos(self, monkeypatch):
    chaos.reset()
    yield
    monkeypatch.delenv(chaos.ENV_HOST, raising=False)
    chaos.reset()

  def test_partition_past_timeout_reads_as_death(self, tiny_state,
                                                 tmp_path, monkeypatch):
    """A wire partition longer than TOS_HOST_TIMEOUT is
    indistinguishable from host death and MUST be handled identically:
    the fleet ejects the silent replica and failover-replays its
    accepted requests bit-identically on the survivor."""
    cfg, state = tiny_state
    monkeypatch.setenv(chaos.ENV_HOST, "decode@0#3:partition:60")
    with _hosts_up(tiny_state, tmp_path, n=2,
                   plane_kw={"timeout": 0.5}) as (addr, plane, versions):
      # only the partitioned host 0 can time out: on a loaded machine a
      # healthy host's sync may come later than 0.5 s too
      plane._hosts[1].__class__ = _NeverSilent
      fl = ServingFleet(
          remote_mod.remote_engine_factory(plane, version=versions[0]),
          num_replicas=2, poll_interval=0.02,
          health_probe=remote_mod.wire_health_probe(addr)).start()
      try:
        work = _workload(13, n=8, budgets=(8, 16))
        frids = [fl.submit(p, max_new_tokens=b) for p, b in work]
        outs = [fl.result(fr, timeout=120) for fr in frids]
        stats = dict(fl.stats)
        states = fl.replica_states()
      finally:
        fl.stop()
      assert fleet_mod.EJECTED in states.values()
      assert stats["ejections"] >= 1 and stats["failovers"] >= 1
      assert stats["shed"] == 0 and stats["replay_mismatches"] == 0
      for (p, b), out in zip(work, outs):
        np.testing.assert_array_equal(
            out, _reference(state.params, cfg, p, b))

  def test_stall_slows_but_never_ejects(self, tiny_state, tmp_path,
                                        monkeypatch):
    """A stalled host (slow sync loop, well under TOS_HOST_TIMEOUT) is
    weather, not death: no ejection, no failover, full parity."""
    cfg, state = tiny_state
    monkeypatch.setenv(chaos.ENV_HOST, "sync@0#5:stall:0.3")
    with _hosts_up(tiny_state, tmp_path, n=2) as (addr, plane, versions):
      fl = ServingFleet(
          remote_mod.remote_engine_factory(plane, version=versions[0]),
          num_replicas=2, poll_interval=0.02).start()
      try:
        work = _workload(17, n=6)
        frids = [fl.submit(p, max_new_tokens=b) for p, b in work]
        outs = [fl.result(fr, timeout=120) for fr in frids]
        stats = dict(fl.stats)
        states = fl.replica_states()
      finally:
        fl.stop()
      assert fleet_mod.EJECTED not in states.values()
      assert stats["ejections"] == 0 and stats["shed"] == 0
      for (p, b), out in zip(work, outs):
        np.testing.assert_array_equal(
            out, _reference(state.params, cfg, p, b))

  def test_host_process_kill_mid_decode_fails_over_bit_identical(
      self, tiny_state, tmp_path, monkeypatch):
    """THE acceptance pin, across a REAL process boundary (it spawns two
    executors: 12 s; also in `make fleet-chaos`): two
    ServingHost processes, TOS_CHAOS_HOST SIGKILLs one mid-decode — the
    fleet ejects it, replays its accepted requests bit-identically on
    the survivor (stream positions exactly-once by the position-stamped
    wire), and a subsequent rolling swap across the process boundary
    sheds zero."""
    cfg, state = tiny_state
    opts = dict(num_slots=2, eos_id=EOS, pad_id=PAD, horizon=2)
    reg = ModelRegistry(str(tmp_path))
    extra = {"model_cfg": host_mod.cfg_wire(cfg), "serve_opts": opts}
    v1 = reg.publish(state.params, step=100, extra=extra)
    v2 = reg.publish(state.params, step=200, extra=extra)
    server = rendezvous.Server(count=1)
    addr = server.start()
    plane = remote_mod.attach_serving_plane(server, timeout=1.0)
    chaos_env = {chaos.ENV_HOST: "decode@0#5:kill"}
    procs = [host_mod.start_host_process(addr, hid,
                                         registry_root=str(tmp_path),
                                         env=chaos_env)
             for hid in range(2)]
    try:
      plane.await_hosts(2, timeout=180)
      fl = ServingFleet(
          remote_mod.remote_engine_factory(plane, version=v1),
          num_replicas=2, poll_interval=0.02,
          health_probe=remote_mod.wire_health_probe(addr)).start()
      try:
        work = _workload(19, n=8, budgets=(8, 16))
        frids = [fl.submit(p, max_new_tokens=b) for p, b in work]
        outs = [fl.result(fr, timeout=300) for fr in frids]
        stats = dict(fl.stats)
        states = fl.replica_states()
        procs[0].join(timeout=60)
        assert procs[0].exitcode == -9          # SIGKILL, not clean exit
        # post-kill rolling swap across the process boundary: the
        # survivor drains, frees its host, rebuilds v2 on it — with
        # requests in flight and nothing shed
        frids2 = [fl.submit(p, max_new_tokens=b) for p, b in work[:4]]
        swap = fl.rolling_swap(
            timeout=120.0,
            engine_factory=remote_mod.remote_engine_factory(plane,
                                                            version=v2),
            version=v2)
        outs2 = [fl.result(fr, timeout=300) for fr in frids2]
        stats2 = dict(fl.stats)
      finally:
        fl.stop()
    finally:
      for hid in plane.host_ids():
        plane.enqueue(hid, {"op": "exit"})
      for p in procs:
        p.join(timeout=15)
        if p.is_alive():
          p.terminate()
      server.stop()
    assert fleet_mod.EJECTED in states.values()
    assert stats["ejections"] >= 1 and stats["failovers"] >= 1
    assert stats["shed"] == 0 and stats["replay_mismatches"] == 0
    assert swap["swapped"] == 1                  # the survivor only
    assert all(r.get("drained") for r in swap["replicas"]
               if "drained" in r)
    assert stats2["shed"] == 0
    for (p, b), out in zip(work, outs):
      np.testing.assert_array_equal(
          out, _reference(state.params, cfg, p, b))
    for (p, b), out in zip(work[:4], outs2):
      np.testing.assert_array_equal(
          out, _reference(state.params, cfg, p, b))

  def test_malformed_host_spec_raises(self, monkeypatch):
    monkeypatch.setenv(chaos.ENV_HOST, "sync@0:partition")
    with pytest.raises(ValueError):
      chaos.check_config()
