"""Continuous-deployment tests: registry + canary controller
(serving/registry.py, serving/deploy.py; docs/ROBUSTNESS.md §Continuous
deployment).

The load-bearing claims: publish is torn-write-proof (a truncated
version deterministically resolves to the previous one), the controller
moves a fleet between versions without shedding a single accepted
request, VERIFY catches a poisoned candidate via greedy bit-parity and
quarantines it forever, and a controller killed at any state boundary
(``TOS_CHAOS_DEPLOY``, ``make deploy-chaos``) leaves a fleet that
``resume()`` converges to ONE consistent version.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.serving import (
    ControllerKilled, DeploymentController, ModelRegistry, ServingEngine,
    ServingFleet)
from tensorflowonspark_tpu.serving import registry as registry_mod
from tensorflowonspark_tpu.utils import chaos
from tensorflowonspark_tpu.utils.checkpoint import params_fingerprint

EOS = 7
PAD = 0


def _tiny(max_seq_len=48, **kw):
  return tfm.TransformerConfig(vocab_size=64, num_layers=2, num_heads=2,
                               d_model=32, d_ff=64,
                               max_seq_len=max_seq_len, remat=False,
                               dtype=jnp.float32, **kw)


@pytest.fixture(scope="module")
def tiny_states():
  """(cfg, [params_v1, params_v2, params_v3]): three 'training runs' —
  distinct seeds stand in for checkpoints at successive steps."""
  cfg = _tiny()
  return cfg, [tfm.create_state(jax.random.PRNGKey(s), cfg,
                                seq_len=16).params for s in (0, 1, 2)]


def _reference(params, cfg, prompt, budget, eos_id=EOS):
  """Single-request decode truncated at its stop — the parity oracle."""
  out = np.asarray(tfm.greedy_generate_kv(
      params, cfg, jnp.asarray(prompt)[None], int(budget), eos_id=eos_id,
      pad_id=PAD))[0]
  gen = out[len(prompt):]
  stops = np.where(gen == eos_id)[0]
  stop = (int(stops[0]) + 1) if len(stops) else int(budget)
  return np.concatenate([np.asarray(prompt), gen[:stop]])


def _workload(seed, n=6, plens=(3, 5, 7), budgets=(4, 6)):
  rng = np.random.RandomState(seed)
  return [(rng.randint(1, 64, (int(rng.choice(plens)),)).astype(np.int32),
           int(rng.choice(budgets))) for _ in range(n)]


def _tree(scale=1.0):
  """A tiny nested-dict params stand-in for registry-only tests (no
  model, no engines — publish/GC/quarantine are pure filesystem)."""
  return {"dense": {"w": np.arange(6, dtype=np.float32) * scale,
                    "b": np.zeros(2, np.float32)},
          "emb": np.ones((3, 2), np.float32) * scale}


def _controller(fleet, reg, cfg, states, probe, **kw):
  def make_factory(params, manifest):
    return lambda: ServingEngine(params, cfg, num_slots=2, eos_id=EOS,
                                 pad_id=PAD, horizon=2)

  def reference_decode(params, prompt, budget):
    return _reference(params, cfg, prompt, budget)

  kw.setdefault("traffic_slice", 0.5)
  kw.setdefault("bake_seconds", 0.2)
  # VERIFY's latency gate compares the medians of three TTFT samples a
  # side, and a fresh canary engine compiles its first prefill shapes
  # inside its own: 0.2x to 13x on a loaded box against the 10x default.
  # The clock decides none of these tests; the gate has its own
  # (test_latency_gate_rolls_back), with a threshold no clock can miss
  kw.setdefault("ttft_degrade_ratio", float("inf"))
  kw.setdefault("spot_checks", 2)
  kw.setdefault("swap_timeout", 120.0)
  return DeploymentController(fleet, reg, make_factory, reference_decode,
                              probe, **kw)


def _fleet_for(reg, cfg, version, replicas=2):
  params, _ = reg.get(version)
  fl = ServingFleet(
      lambda: ServingEngine(params, cfg, num_slots=2, eos_id=EOS,
                            pad_id=PAD, horizon=2),
      num_replicas=replicas).start()
  for rid in fl.replica_states():
    fl.set_replica_version(rid, version)
  return fl


class TestRegistry:
  def test_publish_get_roundtrip(self, tmp_path):
    reg = ModelRegistry(str(tmp_path))
    v1 = reg.publish(_tree(1.0), step=10, lineage={"run": "a"})
    v2 = reg.publish(_tree(2.0), step=20)
    assert (v1, v2) == (1, 2)
    assert reg.versions() == [1, 2] and reg.latest() == 2
    params, manifest = reg.get(v2)
    np.testing.assert_array_equal(params["dense"]["w"],
                                  _tree(2.0)["dense"]["w"])
    assert manifest["step"] == 20
    assert manifest["fingerprint"] == params_fingerprint(_tree(2.0))
    assert reg.manifest(v1)["lineage"] == {"run": "a"}
    # non-dict trees and '/' keys are rejected loudly (path encoding)
    with pytest.raises(TypeError):
      reg.publish([np.zeros(2)], step=1)
    with pytest.raises(ValueError, match="'/'"):
      reg.publish({"a/b": np.zeros(2)}, step=1)

  def test_torn_publish_resolves_to_previous(self, tmp_path):
    """The torn-publish contract: kill the publisher mid-write — here by
    truncating EVERY file of the newest version, params and marker both
    — and the registry deterministically resolves to the previous marked
    version. The torn version's number is never reused."""
    reg = ModelRegistry(str(tmp_path))
    v1 = reg.publish(_tree(1.0), step=10)
    v2 = reg.publish(_tree(2.0), step=20)
    vdir = reg._dir(v2)
    for name in os.listdir(vdir):
      with open(os.path.join(vdir, name), "r+b") as f:
        f.truncate(0)
    assert reg.latest() == v1 and reg.versions() == [v1]
    with pytest.raises(FileNotFoundError, match="no commit marker"):
      reg.get(v2)
    # a fresh reader (a restarted publisher) sees the same resolution
    # and publishes PAST the torn number
    fresh = ModelRegistry(str(tmp_path))
    assert fresh.latest() == v1
    assert fresh.publish(_tree(3.0), step=30) == 3

  def test_corruption_at_rest_detected(self, tmp_path):
    """A readable-but-wrong params file (partial copy, bit rot) must trip
    the manifest fingerprint check in get(), not serve wrong logits."""
    reg = ModelRegistry(str(tmp_path))
    v = reg.publish(_tree(1.0), step=1)
    ppath = os.path.join(reg._dir(v), registry_mod._PARAMS)
    flat = {"dense/w": np.arange(6, dtype=np.float32) * 9.0,
            "dense/b": np.zeros(2, np.float32),
            "emb": np.ones((3, 2), np.float32)}
    with open(ppath, "wb") as f:
      np.savez(f, **flat)
    with pytest.raises(ValueError, match="corrupt at rest"):
      reg.get(v)
    params, _ = reg.get(v, verify=False)       # escape hatch for forensics
    assert params["dense"]["w"][1] == 9.0

  def test_watch_sees_new_version(self, tmp_path):
    reg = ModelRegistry(str(tmp_path))
    v1 = reg.publish(_tree(1.0), step=1)
    assert reg.watch(0.05, last_seen=v1, poll=0.01) is None
    v2 = reg.publish(_tree(2.0), step=2)
    assert reg.watch(5.0, last_seen=v1, poll=0.01) == v2
    assert reg.watch(5.0, last_seen=None, poll=0.01) == v2

  def test_quarantine_hides_and_records(self, tmp_path):
    reg = ModelRegistry(str(tmp_path))
    v1 = reg.publish(_tree(1.0), step=1)
    v2 = reg.publish(_tree(2.0), step=2)
    reg.quarantine(v2, {"reason": "parity: 2/2 diverged", "ok": False})
    assert reg.latest() == v1
    assert reg.versions() == [v1]
    assert reg.versions(include_quarantined=True) == [v1, v2]
    assert reg.is_quarantined(v2)
    rec = reg.quarantine_record(v2)
    assert rec["verdict"]["reason"].startswith("parity")
    # a watcher can never be handed the quarantined version again
    assert reg.watch(0.05, last_seen=v1, poll=0.01) is None

  def test_gc_respects_refs_quarantine_and_newest(self, tmp_path):
    reg = ModelRegistry(str(tmp_path), keep=1)
    vs = [reg.publish(_tree(float(i)), step=i) for i in range(1, 5)]
    reg.acquire(vs[1])               # a fleet still serves v2
    reg.quarantine(vs[2])            # v3 failed VERIFY: the record stays
    assert reg.gc() == [vs[0]]
    assert not os.path.isdir(reg._dir(vs[0]))
    for v in vs[1:]:
      assert os.path.isdir(reg._dir(v))
    reg.release(vs[1])
    assert reg.gc() == [vs[1]]
    assert os.path.isdir(reg._dir(vs[2]))    # quarantined: never GCed
    assert reg.latest() == vs[3]

  def test_publish_on_checkpoint_rides_save_cadence(self, tmp_path):
    """The trainer side of the loop: a REAL CheckpointManager save that
    COMMITS (marker durable) publishes the params as the next registry
    version, with the checkpoint lineage folded into the manifest."""
    from tensorflowonspark_tpu.utils.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path / "ckpt"), save_interval_steps=1)
    reg = ModelRegistry(str(tmp_path / "reg"))
    reg.publish_on_checkpoint(mgr, get_params=lambda s: s,
                              lineage={"run": "trainer0"})
    state = _tree(4.0)
    assert mgr.save(3, state, is_chief=True, manifest={"note": "x"})
    mgr.wait()
    v = reg.latest()
    assert v == 1
    params, manifest = reg.get(v)
    np.testing.assert_array_equal(params["dense"]["w"],
                                  state["dense"]["w"])
    assert manifest["step"] == 3
    assert manifest["lineage"]["run"] == "trainer0"
    assert manifest["lineage"]["checkpoint_manifest"] == {"note": "x"}
    assert "ckpt" in manifest["lineage"]["checkpoint_dir"]


class TestServingEngineCachePin:
  @pytest.mark.slow  # ~14s; re-proven by the tier-1 happy path; tier-1 budget
  def test_republished_same_shape_params_not_served_stale(
      self, tiny_states):
    """The predict-fn engine cache keys on param CONTENT, not just the
    serving config: serving a republished same-shape tree through the
    same predict_fn must produce that tree's outputs, never the cached
    engine's stale weights (the registry re-serve bug).

    Stronger tier-1 sibling: TestDeployController::
    test_happy_path_promotes_fleet_wide serves a republished same-shape
    v2 through the same predict-fn cache post-promote and asserts
    bit-parity against the v2 reference — the re-serve bug would fail
    it. Still runs via `make test`."""
    cfg, states = tiny_states
    fn = tfm.make_serving_predict_fn(cfg, 4, eos_id=EOS, pad_id=PAD,
                                     num_slots=2)
    prompts = [np.asarray([1, 2, 3], np.int32),
               np.asarray([4, 5], np.int32)]
    col = np.empty(2, object)
    col[:] = prompts
    out1 = fn(states[0], {"x": col})["tokens"]
    out2 = fn(states[1], {"x": col})["tokens"]    # new version, same shape
    for i, p in enumerate(prompts):
      r1 = _reference(states[0], cfg, p, 4)
      r2 = _reference(states[1], cfg, p, 4)
      np.testing.assert_array_equal(out1[i, :len(r1)], r1)
      np.testing.assert_array_equal(out2[i, :len(r2)], r2)
    # identity fast path: the SAME tree object hits without rehashing
    out2b = fn(states[1], {"x": col})["tokens"]
    np.testing.assert_array_equal(out2, out2b)


class TestFleetScaleUp:
  def test_on_saturated_adds_replica_up_to_cap(self, tiny_states):
    cfg, states = tiny_states
    factory = lambda: ServingEngine(states[0], cfg, num_slots=2,  # noqa: E731
                                    eos_id=EOS, pad_id=PAD, horizon=2)
    with ServingFleet(factory, num_replicas=1, max_replicas=2) as fl:
      assert fl.num_replicas == 1
      assert fl.on_saturated() is True           # below cap: add one
      assert fl.num_replicas == 2
      assert fl.stats["scale_ups"] == 1
      assert fl.on_saturated() is False          # at cap: signal-only
      assert fl.num_replicas == 2
      work = _workload(5, n=6)
      outs = fl.generate([p for p, _ in work],
                         max_new_tokens=max(b for _, b in work),
                         timeout=120)
      for (p, _), o in zip(work, outs):
        np.testing.assert_array_equal(
            o, _reference(states[0], cfg, p,
                          max(b for _, b in work)))
      assert any(e["event"] == "scale_up" for e in fl.events)

  def test_hook_off_by_default_and_cap_validated(self, tiny_states):
    cfg, states = tiny_states
    factory = lambda: ServingEngine(states[0], cfg, num_slots=2,  # noqa: E731
                                    eos_id=EOS, pad_id=PAD, horizon=2)
    with ServingFleet(factory, num_replicas=1) as fl:
      assert fl.max_replicas is None
      assert fl.on_saturated() is False
      assert fl.num_replicas == 1
    with pytest.raises(ValueError):
      ServingFleet(factory, num_replicas=3, max_replicas=2)


class TestDeployController:
  def test_happy_path_promotes_fleet_wide(self, tmp_path, tiny_states):
    """CANARY → VERIFY → PROMOTE with nothing injected: the candidate
    takes one replica, the canary slice routes live traffic at it (the
    version stamp partitions the timing ledger), parity holds, and the
    whole fleet converges on the new version zero-shed."""
    cfg, states = tiny_states
    reg = ModelRegistry(str(tmp_path))
    v1 = reg.publish(states[0], step=100)
    v2 = reg.publish(states[1], step=200)
    work = _workload(7, n=6)
    fl = _fleet_for(reg, cfg, v1)
    snap = fl.stats_snapshot()
    try:
      ctl = _controller(fl, reg, cfg, states, work[:2],
                        baseline_version=v1)
      verdict = ctl.deploy(v2, bake_traffic=work)
      assert verdict["ok"] and verdict.get("promoted")
      assert verdict["parity"]["mismatches"] == 0
      assert verdict["canary_samples"] >= 1      # the slice really routed
      assert set(fl.served_versions().values()) == {v2}
      assert ctl.current_version == v2 and ctl.state == "idle"
      assert ctl.stats["promotions"] == 1 and ctl.stats["rollbacks"] == 0
      # post-promote requests serve v2 bit-identically and stamp it
      frid = fl.submit(work[0][0], max_new_tokens=work[0][1])
      freq = fl.request(frid)
      out = fl.result(frid, timeout=120)
      np.testing.assert_array_equal(
          out, _reference(states[1], cfg, work[0][0], work[0][1]))
      assert freq.timing()["model_version"] == v2
      assert snap.delta().get("shed", 0) == 0
      # retention moved with the rollout: the new version is pinned
      assert reg.refcount(v2) == 1 and reg.refcount(v1) == 0
      st = ctl.status()
      assert st["state"] == "idle" and st["version"] == v2
    finally:
      fl.stop()

  def test_latency_gate_rolls_back(self, tmp_path, tiny_states):
    """VERIFY's latency gate, decided by the threshold and not by the
    clock: with a ratio no canary can stay under, a candidate whose
    parity is clean is still rolled back bit-identically and quarantined,
    and the verdict names the latency."""
    cfg, states = tiny_states
    reg = ModelRegistry(str(tmp_path))
    v1 = reg.publish(states[0], step=100)
    v2 = reg.publish(states[1], step=200)
    work = _workload(7, n=6)
    fl = _fleet_for(reg, cfg, v1)
    try:
      ctl = _controller(fl, reg, cfg, states, work[:2],
                        baseline_version=v1, ttft_degrade_ratio=1e-9)
      verdict = ctl.deploy(v2, bake_traffic=work)
      assert not verdict["ok"] and "latency" in verdict["reason"]
      assert verdict["parity"]["mismatches"] == 0
      assert verdict["canary_samples"] >= 1
      assert verdict["baseline_samples"] >= 1
      assert verdict["rollback_bit_identical"] is True
      assert reg.is_quarantined(v2)
      assert set(fl.served_versions().values()) == {v1}
      assert ctl.stats["rollbacks"] == 1 and ctl.stats["promotions"] == 0
    finally:
      fl.stop()


class TestDeployChaos:
  """TOS_CHAOS_DEPLOY-driven proofs (make deploy-chaos): controller
  death and candidate poisoning are injected deterministically at state
  boundaries, never simulated by hand. Chaos counters are per-process —
  every test resets them."""

  pytestmark = pytest.mark.chaos

  @pytest.fixture(autouse=True)
  def _fresh_chaos(self, monkeypatch):
    chaos.reset()
    yield
    monkeypatch.delenv(chaos.ENV_DEPLOY, raising=False)
    chaos.reset()

  def test_poisoned_candidate_caught_quarantined_rolled_back(
      self, tmp_path, tiny_states, monkeypatch):
    """The poisoned-candidate contract: params corrupted at the canary
    engine build (PAST the registry fingerprint check — corruption in
    the serving path, not at rest) must be caught by VERIFY's greedy
    parity spot-checks, rolled back to outputs BIT-IDENTICAL to the
    pre-canary baseline, and quarantined so no watcher ever redeploys
    it."""
    cfg, states = tiny_states
    reg = ModelRegistry(str(tmp_path))
    v1 = reg.publish(states[0], step=100)
    v2 = reg.publish(states[1], step=200)
    work = _workload(11, n=6)
    fl = _fleet_for(reg, cfg, v1)
    snap = fl.stats_snapshot()
    monkeypatch.setenv(chaos.ENV_DEPLOY, "canary:poison")
    try:
      ctl = _controller(fl, reg, cfg, states, work[:2],
                        baseline_version=v1)
      verdict = ctl.deploy(v2, bake_traffic=work)
      assert not verdict["ok"]
      assert verdict["parity"]["mismatches"] > 0
      assert verdict["rollback_bit_identical"] is True
      assert reg.is_quarantined(v2)
      assert reg.latest() == v1                  # watch() can't see v2
      assert set(fl.served_versions().values()) == {v1}
      assert ctl.current_version == v1 and ctl.state == "idle"
      assert ctl.stats["rollbacks"] == 1
      assert ctl.stats["parity_failures"] > 0
      assert snap.delta().get("shed", 0) == 0
      assert reg.quarantine_record(v2)["verdict"]["reason"]
    finally:
      fl.stop()

  def test_kill_mid_promote_resume_converges(self, tmp_path, tiny_states,
                                             monkeypatch):
    """The headline chaos contract: the controller dies at the first
    promote boundary, leaving a MIXED-version fleet — which must keep
    completing requests — and resume() converges every replica to the
    candidate (it was already serving on the canary) with zero shed,
    every output after it bit-identical to the candidate's reference."""
    cfg, states = tiny_states
    reg = ModelRegistry(str(tmp_path))
    v1 = reg.publish(states[0], step=100)
    v2 = reg.publish(states[1], step=200)
    work = _workload(13, n=6)
    fl = _fleet_for(reg, cfg, v1)
    snap = fl.stats_snapshot()
    monkeypatch.setenv(chaos.ENV_DEPLOY, "promote:kill")
    try:
      ctl = _controller(fl, reg, cfg, states, work[:2],
                        baseline_version=v1)
      with pytest.raises(ControllerKilled):
        ctl.deploy(v2, bake_traffic=work)
      served = fl.served_versions()
      assert set(served.values()) == {v1, v2}    # genuinely mid-promote
      # the mixed fleet still serves: each output matches ITS replica's
      # version reference (both versions are internally bit-exact)
      for p, b in work:
        frid = fl.submit(p, max_new_tokens=b)
        freq = fl.request(frid)
        out = fl.result(frid, timeout=120)
        ver = freq.timing()["model_version"]
        np.testing.assert_array_equal(
            out, _reference(states[ver - 1], cfg, p, b))
      monkeypatch.delenv(chaos.ENV_DEPLOY)
      chaos.reset()
      rep = ctl.resume(timeout=120.0)
      assert rep["target"] == v2 and rep["swapped"] >= 1
      assert set(fl.served_versions().values()) == {v2}
      assert ctl.current_version == v2
      frids = [fl.submit(p, max_new_tokens=b) for p, b in work]
      for (p, b), frid in zip(work, frids):
        np.testing.assert_array_equal(
            fl.result(frid, timeout=120), _reference(states[1], cfg, p, b))
      assert snap.delta().get("shed", 0) == 0
      assert reg.refcount(v2) == 1
    finally:
      fl.stop()

  def test_kill_mid_canary_resume_keeps_baseline(self, tmp_path,
                                                 tiny_states,
                                                 monkeypatch):
    """A kill BEFORE the canary swap leaves the fleet untouched on the
    baseline; resume() must keep it there (the candidate is newer but
    nobody serves it — converging means consistency, not eagerness)."""
    cfg, states = tiny_states
    reg = ModelRegistry(str(tmp_path))
    v1 = reg.publish(states[0], step=100)
    v2 = reg.publish(states[1], step=200)
    work = _workload(17, n=4)
    fl = _fleet_for(reg, cfg, v1)
    monkeypatch.setenv(chaos.ENV_DEPLOY, "canary:kill")
    try:
      ctl = _controller(fl, reg, cfg, states, work[:2],
                        baseline_version=v1)
      with pytest.raises(ControllerKilled):
        ctl.deploy(v2)
      assert set(fl.served_versions().values()) == {v1}
      monkeypatch.delenv(chaos.ENV_DEPLOY)
      chaos.reset()
      rep = ctl.resume(timeout=120.0)
      assert rep["target"] == v1 and rep["swapped"] == 0
      assert set(fl.served_versions().values()) == {v1}
      assert ctl.state == "idle" and ctl.candidate_version is None
    finally:
      fl.stop()

  def test_malformed_deploy_spec_rejected_at_startup(self, monkeypatch):
    monkeypatch.setenv(chaos.ENV_DEPLOY, "promote@kill")
    with pytest.raises(ValueError, match="malformed deploy spec"):
      chaos.check_config()
    monkeypatch.setenv(chaos.ENV_DEPLOY, "canary:poison,promote:stall:0.1")
    chaos.check_config()                         # well-formed: accepted
