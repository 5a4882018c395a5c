"""Elastic multi-group training tests (parallel/groups.py).

The sync plane's round protocol (deadline-bounded merges, miss-driven
eviction, catch-up re-admission) is unit-tested on a fake clock; the
GroupSet runtime is driven end to end on CPU groups — including the
chaos drives behind `make elastic-chaos`: whole-group kill mid-training
with no global stall, eviction + re-admit, and the resharded restore
(checkpoint saved at one group count, resumed at another, step counter
and loss trajectory intact). Supervisor resize paths (commit-shrink /
readmit) run against a stub ClusterSupervisor (the test_cluster idiom).
"""

import threading
import time

import numpy as np
import pytest

from tensorflowonspark_tpu.control import rendezvous
from tensorflowonspark_tpu.parallel import groups as G
from tensorflowonspark_tpu.utils import chaos


@pytest.fixture(autouse=True)
def _fresh_chaos_counters():
  chaos.reset()
  yield
  chaos.reset()


def _leaf(arr):
  a = np.asarray(arr)
  return {"dtype": str(a.dtype), "shape": list(a.shape), "data": a.tobytes()}


def _leaf_np(rec):
  return np.frombuffer(rec["data"], dtype=rec["dtype"]).reshape(rec["shape"])


def _harness(dim=8, batch=4, lr=0.05):
  """Tiny linear-regression build_fn/batch_fn pair: deterministic data
  keyed by (group_id, step) — the GroupSet data-position contract."""
  import jax.numpy as jnp
  import optax
  from flax.training import train_state

  def build_fn(mesh):
    del mesh
    params = {"w": jnp.zeros((dim,), jnp.float32)}
    state = train_state.TrainState.create(apply_fn=None, params=params,
                                          tx=optax.sgd(lr))

    def loss_fn(p, b):
      pred = b["x"] @ p["w"]
      return jnp.mean((pred - b["y"]) ** 2)

    return state, loss_fn

  w_true = np.arange(dim, dtype="float32") / dim

  def batch_fn(group_id, step):
    rng = np.random.RandomState(1000 * group_id + step)
    x = rng.rand(batch, dim).astype("float32")
    return {"x": x, "y": x @ w_true}

  return build_fn, batch_fn


# ---------------------------------------------------------------------------
# payload codec + merge
# ---------------------------------------------------------------------------


class TestCodec:
  def test_pack_unpack_roundtrip(self):
    tree = {"a": np.arange(6, dtype="float32").reshape(2, 3),
            "b": {"c": np.array(7, dtype="int32")}}
    out = G.unpack_tree(G.pack_tree(tree), tree)
    np.testing.assert_array_equal(out["a"], tree["a"])
    np.testing.assert_array_equal(out["b"]["c"], tree["b"]["c"])

  def test_merge_weighted_mean_float_first_wins_rest(self):
    a = [_leaf(np.array([1.0, 2.0], "float32")), _leaf(np.array(3, "int32"))]
    b = [_leaf(np.array([3.0, 6.0], "float32")), _leaf(np.array(9, "int32"))]
    merged = G.merge_payloads([(1.0, a), (3.0, b)])
    # (1*[1,2] + 3*[3,6]) / 4 = [2.5, 5.0]
    np.testing.assert_allclose(_leaf_np(merged[0]), [2.5, 5.0])
    assert int(_leaf_np(merged[1])) == 3        # non-float: first wins

  def test_unpack_leaf_count_mismatch_raises(self):
    tree = {"a": np.zeros(2, "float32")}
    with pytest.raises(ValueError, match="leaves"):
      G.unpack_tree(G.pack_tree(tree) * 2, tree)


# ---------------------------------------------------------------------------
# SyncPlane round protocol (fake clock)
# ---------------------------------------------------------------------------


class TestSyncPlane:
  def _plane(self, **kw):
    clk = [0.0]
    kw.setdefault("sync_timeout", 5.0)
    plane = G.SyncPlane(time_fn=lambda: clk[0], **kw)
    return plane, clk

  def test_round_completes_when_all_members_contribute(self):
    plane, _ = self._plane()
    plane.join(0)
    plane.join(1)
    p = [_leaf(np.array([2.0], "float32"))]
    q = [_leaf(np.array([4.0], "float32"))]
    plane.contribute(0, 1, p, weight=1.0, step=4)
    assert not plane.poll(1)["done"]
    plane.contribute(1, 1, q, weight=1.0, step=4)
    resp = plane.poll(1)
    assert resp["done"] and resp["members"] == [0, 1]
    np.testing.assert_allclose(_leaf_np(resp["payload"][0]), [3.0])
    assert plane.status()["step"] == 4

  def test_deadline_merges_with_whoever_showed_up(self):
    plane, clk = self._plane(sync_timeout=5.0)
    plane.join(0)
    plane.join(1)
    plane.contribute(0, 1, [_leaf(np.array([2.0], "float32"))], step=4)
    assert not plane.poll(1)["done"]
    clk[0] = 6.0          # past the deadline armed by the 1st contribution
    resp = plane.poll(1)
    assert resp["done"] and resp["denominator"] == 1

  def test_miss_limit_evicts_and_rejects_stale_contribution(self):
    plane, clk = self._plane(sync_timeout=5.0, miss_limit=2)
    plane.join(0)
    plane.join(1)
    for rnd in (1, 2):
      plane.contribute(0, rnd, [_leaf(np.array([1.0], "float32"))], step=rnd)
      clk[0] += 6.0
      assert plane.poll(rnd)["done"]
    assert 1 in plane.lost
    stale = plane.contribute(1, 3, [_leaf(np.array([9.0], "float32"))])
    assert stale["lost"] and not stale["accepted"]
    # re-join clears the eviction and hands back the catch-up payload
    resp = plane.join(1)
    assert resp["payload"] is not None and 1 in plane.active

  def test_mid_round_join_does_not_stall_open_round(self):
    plane, _ = self._plane()
    plane.join(0)
    plane.contribute(0, 1, [_leaf(np.array([1.0], "float32"))])
    plane.join(1)         # joins mid-round: participates from round 2
    resp = plane.poll(1)
    assert resp["done"] and resp["members"] == [0]

  def test_seed_primes_step_and_catch_up(self):
    plane, _ = self._plane()
    payload = [_leaf(np.array([5.0], "float32"))]
    plane.seed(12, payload)
    resp = plane.join(3)
    assert resp["step"] == 12
    np.testing.assert_allclose(_leaf_np(resp["payload"][0]), [5.0])


# ---------------------------------------------------------------------------
# the SYNC/SYNCQ/GROUP verbs over a live server
# ---------------------------------------------------------------------------


class TestSyncWire:
  def test_two_clients_sync_through_live_server(self):
    server = rendezvous.Server(1)
    server.start()
    try:
      G.attach_sync_plane(server, sync_timeout=10.0)
      results = {}

      # members join before anyone syncs, as GroupSet's do: a round's
      # membership is frozen when its first contribution arrives, so a
      # first sync that beats the other member's arrival merges alone
      clients = {gid: G.GroupSyncClient(server.addr, gid,
                                        request_timeout=5.0)
                 for gid in (0, 1)}
      for c in clients.values():
        assert c.join()["ok"]

      def member(gid, value, weight):
        try:
          tree = {"w": np.array([value], "float32")}
          results[gid] = clients[gid].sync(1, tree, weight=weight, step=4,
                                           timeout=15.0)
        finally:
          clients[gid].close()

      threads = [threading.Thread(target=member, args=(0, 2.0, 1.0)),
                 threading.Thread(target=member, args=(1, 6.0, 3.0))]
      for t in threads:
        t.start()
      for t in threads:
        t.join(timeout=30)
      merged0, members0 = results[0]
      merged1, _ = results[1]
      # (1*2 + 3*6) / 4 = 5.0, identical on both sides
      np.testing.assert_allclose(merged0["w"], [5.0])
      np.testing.assert_allclose(merged1["w"], [5.0])
      assert members0 == [0, 1]
      assert server.sync_plane.status()["rounds_completed"] == 1
    finally:
      server.stop()

  def test_sync_verbs_error_without_attached_plane(self):
    server = rendezvous.Server(1)
    server.start()
    try:
      client = rendezvous.Client(server.addr, timeout=5.0)
      resp = client._request({"type": "GROUP", "action": "join",
                              "group_id": 0})
      assert resp["type"] == "ERROR"
      client.close()
    finally:
      server.stop()


# ---------------------------------------------------------------------------
# GroupSet end to end
# ---------------------------------------------------------------------------


class TestGroupSet:
  def test_groups_converge_and_agree_at_boundaries(self):
    build_fn, batch_fn = _harness()
    gs = G.GroupSet(build_fn, batch_fn, num_groups=2, sync_every=4,
                    sync_timeout=15.0)
    try:
      gs.run(16)
      assert gs.wait(timeout=120)
      for g in gs.groups.values():
        assert g.exit_reason == "completed" and g.steps == 16
      p0 = G.pack_tree(gs.groups[0].state.params)
      p1 = G.pack_tree(gs.groups[1].state.params)
      assert all(a["data"] == b["data"] for a, b in zip(p0, p1)), \
          "post-sync params must be bit-identical across groups"
      assert gs.plane.status()["rounds_completed"] == 4
      losses = gs.groups[0].losses
      assert losses[-1] < losses[0], "training must actually converge"
    finally:
      gs.close()

  @pytest.mark.chaos
  def test_group_kill_no_global_stall_then_readmit(self, monkeypatch):
    """The headline chaos drive: a whole group dies mid-training (no
    goodbye, no contribution) — the survivor keeps stepping to completion
    with the sync denominator shrunk (never a global stall), the plane
    evicts the dead group, and readmit() brings it back caught-up."""
    monkeypatch.setenv(chaos.ENV_GROUP, "kill@1#2")
    build_fn, batch_fn = _harness()
    gs = G.GroupSet(build_fn, batch_fn, num_groups=2, sync_every=4,
                    sync_timeout=1.0, miss_limit=2)
    try:
      gs.run(24)
      assert gs.wait(timeout=120)
      assert gs.groups[1].exit_reason == "chaos-kill"
      assert gs.groups[0].exit_reason == "completed"
      assert gs.groups[0].steps == 24, "survivor must reach the target"
      assert 1 in gs.plane.lost
      kinds = [e["event"] for e in gs.events]
      assert "group-killed" in kinds and "plane-lost" in kinds
      # re-admit: fresh group pulls current weights and finishes the run
      monkeypatch.delenv(chaos.ENV_GROUP)
      chaos.reset()
      g = gs.readmit(1)
      assert g.steps >= 20, "readmitted group must catch up, not rewind"
      assert gs.wait(timeout=120)
      assert gs.groups[1].exit_reason == "completed"
      assert gs.plane.status()["groups_active"] == 2
    finally:
      gs.close()

  @pytest.mark.chaos
  def test_stalled_group_misses_deadline_and_self_readmits(self, monkeypatch):
    """A mid-sync stall: group 1 sleeps through round 1, the survivor's
    round merges at the deadline (denominator 1), the plane evicts the
    straggler at miss_limit, and its stale contribution is rejected —
    it self-readmits via the join catch-up and both groups finish."""
    monkeypatch.setenv(chaos.ENV_GROUP, "stall@1#1:2.0")
    build_fn, batch_fn = _harness()
    gs = G.GroupSet(build_fn, batch_fn, num_groups=2, sync_every=4,
                    sync_timeout=0.5, miss_limit=1)
    try:
      gs.run(8)
      assert gs.wait(timeout=120)
      for g in gs.groups.values():
        assert g.exit_reason == "completed" and g.steps == 8
      kinds = [e["event"] for e in gs.events]
      assert "plane-lost" in kinds, "the straggler must get evicted"
      assert "group-readmitted" in kinds, \
          "eviction must resolve via the catch-up re-join, not a wedge"
    finally:
      gs.close()

  @pytest.mark.chaos
  def test_reshard_restore_step_counter_and_loss_continuity(self, tmp_path):
    """Save at 2 groups, restore at 3 and at 1: every topology resumes
    from the same step with the same weights (restore = broadcast —
    group interchangeability), and the chief group's post-restore loss
    trajectory is BIT-IDENTICAL across topologies (the loss-continuity
    pin: same step counter -> same batches -> same losses)."""
    from tensorflowonspark_tpu.utils.checkpoint import CheckpointManager
    build_fn, batch_fn = _harness()
    gs = G.GroupSet(build_fn, batch_fn, num_groups=2, sync_every=4,
                    sync_timeout=15.0)
    try:
      gs.run(8)
      assert gs.wait(timeout=120)
      mgr = CheckpointManager(str(tmp_path / "ck"), save_interval_steps=1)
      assert gs.save(mgr, force=True)
      mgr.wait()
      assert mgr.manifest() == {
          "schema": 1, "kind": "groupset", "num_groups": 2,
          "groups": [0, 1], "step": 8, "sync_every": 4, "sync_round": 2}
      saved = G.pack_tree(gs.groups[0].state.params)
    finally:
      gs.close()

    trajectories = {}
    for n in (3, 1):
      gs2 = G.GroupSet(build_fn, batch_fn, num_groups=n, sync_every=4,
                       sync_timeout=15.0)
      try:
        mgr2 = CheckpointManager(str(tmp_path / "ck"), save_interval_steps=1)
        next_step = gs2.restore_or(mgr2)
        assert next_step == 9, "step counter must survive the reshard"
        for g in gs2.groups.values():
          assert g.steps == 8
          restored = G.pack_tree(g.state.params)
          assert all(a["data"] == b["data"]
                     for a, b in zip(saved, restored)), \
              "every group must adopt the checkpointed weights bitwise"
        gs2.run(12)
        assert gs2.wait(timeout=120)
        assert all(g.exit_reason == "completed" and g.steps == 12
                   for g in gs2.groups.values())
        trajectories[n] = list(gs2.groups[0].losses)
      finally:
        gs2.close()
    assert trajectories[3] == trajectories[1], \
        "chief-group loss continuity must not depend on the group count"


# ---------------------------------------------------------------------------
# chaos grammar (TOS_CHAOS_GROUP)
# ---------------------------------------------------------------------------


class TestGroupChaosGrammar:
  def test_malformed_spec_raises_at_first_consult(self, monkeypatch):
    monkeypatch.setenv(chaos.ENV_GROUP, "explode@1#2")
    with pytest.raises(ValueError, match="malformed group spec"):
      chaos.check_config()

  def test_kill_verdict_counts_per_group(self, monkeypatch):
    monkeypatch.setenv(chaos.ENV_GROUP, "kill@1#2")
    assert chaos.group_fault(0) is None
    assert chaos.group_fault(1) is None       # @1 occurrence 1
    assert chaos.group_fault(1) == "kill"     # @1 occurrence 2
    assert chaos.group_fault(1) is None       # budget spent

  def test_global_count_and_stall(self, monkeypatch):
    monkeypatch.setenv(chaos.ENV_GROUP, "kill#3,stall#1:0.2")
    t0 = time.monotonic()
    assert chaos.group_fault(0) is None       # 1st overall: stalls
    assert time.monotonic() - t0 >= 0.2
    assert chaos.group_fault(1) is None
    assert chaos.group_fault(0) == "kill"     # 3rd overall

  def test_disarmed_is_noop(self, monkeypatch):
    monkeypatch.delenv(chaos.ENV_GROUP, raising=False)
    assert chaos.group_fault(5) is None


# ---------------------------------------------------------------------------
# supervisor resize paths (stub ClusterSupervisor — test_cluster idiom)
# ---------------------------------------------------------------------------


def _stub_supervisor(server, meta):
  from tensorflowonspark_tpu.cluster import ClusterSupervisor
  return ClusterSupervisor(engine=None, server=server, node_job=None,
                           cluster_meta=meta, cluster_info=[],
                           engine_ids=[0, 1], tf_status={"error": None},
                           max_restarts=2)


class TestSupervisorResize:
  def test_commit_shrink_evicts_group_and_is_fatal_only_when_empty(self):
    server = rendezvous.Server(2)
    plane = G.attach_sync_plane(server, sync_timeout=5.0)
    plane.join(0)
    plane.join(1)
    sup = _stub_supervisor(server, {"group_map": {0: 0, 1: 1},
                                    "elastic": True})
    sup._commit_shrink(1, 1, attempts=2)
    assert 1 in plane.lost
    ev = [e for e in sup.events if e["kind"] == "resize-shrink"][0]
    assert ev["executor_id"] == 1 and ev["group"] == 1
    assert ev["attempts"] == 2 and ev["groups_active"] == 1
    assert sup.tf_status["error"] is None, \
        "a survivable shrink must not fail the job"
    sup._commit_shrink(0, 0, attempts=2)
    assert "all training groups lost" in sup.tf_status["error"]

  def test_recover_give_up_becomes_shrink_only_in_elastic_mode(self):
    for elastic in (True, False):
      server = rendezvous.Server(2)
      plane = G.attach_sync_plane(server, sync_timeout=5.0)
      plane.join(0)
      plane.join(1)
      meta = {"group_map": {0: 0, 1: 1}, "elastic": elastic,
              "cluster_template": {"worker": [0, 1]}}
      sup = _stub_supervisor(server, meta)
      sup._attempts[1] = sup.max_restarts        # budget already spent
      sup._recover(1)
      kinds = [e["kind"] for e in sup.events]
      if elastic:
        assert "resize-shrink" in kinds and "gave-up" not in kinds
        assert sup.tf_status["error"] is None
      else:
        assert "gave-up" in kinds and "resize-shrink" not in kinds
        gave = [e for e in sup.events if e["kind"] == "gave-up"][0]
        assert gave["attempts"] == 2 and gave["group"] == 1
        assert "restart budget" in sup.tf_status["error"]

  def test_readmit_resets_budget_and_rearms_liveness(self):
    server = rendezvous.Server(2, heartbeat_interval=0.1)
    G.attach_sync_plane(server, sync_timeout=5.0)
    sup = _stub_supervisor(server, {"group_map": {0: 0, 1: 1},
                                    "elastic": True})
    sup._given_up.add(1)
    sup._attempts[1] = 2
    # an old-incarnation beat confirmed the executor: without the rearm
    # the strict deadline would re-declare death mid-bring-up
    server.liveness.beat(1)
    assert 1 in server.liveness._confirmed
    sup.readmit(1)
    assert 1 not in sup._given_up and 1 not in sup._attempts
    assert 1 not in server.liveness._confirmed, \
        "readmit must re-arm the startup grace (drop confirmation)"
    ev = [e for e in sup.events if e["kind"] == "resize-readmit"][0]
    assert ev["executor_id"] == 1 and ev["group"] == 1
