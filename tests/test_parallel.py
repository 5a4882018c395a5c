"""SPMD layer tests on the virtual 8-device CPU mesh: mesh construction,
collectives, ring attention exactness, and the sharded train-step factory.

This is the test tier SURVEY.md §4 prescribes for multi-device behavior
(xla_force_host_platform_device_count — the analog of the reference's
2-worker standalone cluster).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from tensorflowonspark_tpu.parallel import collectives as C
from tensorflowonspark_tpu.parallel import mesh as M
from tensorflowonspark_tpu.parallel import ring_attention as RA
from tensorflowonspark_tpu.parallel import sharding as SH


@pytest.fixture(scope="module")
def devices():
  d = jax.devices()
  if len(d) < 8:
    pytest.skip("needs 8 virtual devices")
  return d


class TestMesh:
  def test_wildcard_absorbs(self, devices):
    mesh = M.build_mesh(M.MeshSpec(data=-1, tensor=2), devices=devices)
    assert mesh.shape[M.AXIS_DATA] == 4
    assert mesh.shape[M.AXIS_TENSOR] == 2

  def test_explicit_exact(self, devices):
    mesh = M.build_mesh(M.MeshSpec(data=2, sequence=2, tensor=2),
                        devices=devices)
    assert dict(mesh.shape)[M.AXIS_SEQUENCE] == 2

  def test_mismatch_raises(self, devices):
    with pytest.raises(ValueError, match="devices"):
      M.build_mesh(M.MeshSpec(data=3, tensor=2), devices=devices)

  def test_two_wildcards_raise(self, devices):
    with pytest.raises(ValueError, match="-1"):
      M.build_mesh(M.MeshSpec(data=-1, tensor=-1), devices=devices)

  def test_axis_size(self, devices):
    mesh = M.build_mesh(M.MeshSpec(data=2, fsdp=2, tensor=2),
                        devices=devices)
    assert M.axis_size(mesh, M.AXIS_DATA, M.AXIS_FSDP) == 4
    assert M.data_axes(mesh) == (M.AXIS_DATA, M.AXIS_FSDP)


class _FakeTPU:
  """Mock device carrying the attributes mesh_utils inspects."""
  platform = "tpu"
  device_kind = "TPU v5e"

  def __init__(self, i, coords, slice_index=0, process_index=0):
    self.id = i
    self.coords = coords
    self.core_on_chip = 0
    self.process_index = process_index
    self.slice_index = slice_index

  def __repr__(self):
    return "FakeTPU(%d, %r, slice=%d)" % (self.id, self.coords,
                                          self.slice_index)


class TestTopologyMesh:
  """build_mesh must honor physical topology on TPU (VERDICT r2 item 3):
  the tensor axis lands on ICI neighbors even when jax.devices() enumerates
  chips out of physical order."""

  def _scrambled_grid(self):
    coords = [(x, y, 0) for y in range(2) for x in range(4)]
    order = [0, 3, 1, 2, 7, 4, 6, 5]
    return [_FakeTPU(i, coords[order[i]]) for i in range(8)]

  def test_tensor_axis_lands_on_neighbors(self):
    mesh = M.build_mesh(M.MeshSpec(data=-1, tensor=4),
                        devices=self._scrambled_grid())
    arr = np.asarray(mesh.devices).reshape(2, 4)
    for row in arr:
      xs = sorted(d.coords[0] for d in row)
      ys = {d.coords[1] for d in row}
      assert xs == [0, 1, 2, 3], "tensor axis straddles the grid: %r" % row
      assert len(ys) == 1, "tensor axis crosses rows: %r" % row

  def test_hybrid_mesh_puts_data_on_dcn(self):
    """Two slices: the data axis absorbs the slice count; every
    non-data axis stays inside one slice (ICI), per SURVEY §2.4."""
    devs = []
    for s in range(2):
      for i in range(4):
        devs.append(_FakeTPU(s * 4 + i, (i % 2, i // 2, 0), slice_index=s,
                             process_index=s))
    mesh = M.build_mesh(M.MeshSpec(data=2, tensor=4), devices=devs)
    arr = np.asarray(mesh.devices).reshape(2, 4)
    for data_idx in range(2):
      slices = {d.slice_index for d in arr[data_idx]}
      assert len(slices) == 1, \
          "tensor axis crosses the DCN boundary: %r" % arr[data_idx]

  def test_cpu_devices_fall_back_to_enumeration(self, devices):
    mesh = M.build_mesh(M.MeshSpec(data=-1), devices=devices)
    assert list(np.asarray(mesh.devices).ravel()) == list(devices)

  def test_unabsorbable_slice_count_falls_back(self, caplog):
    """3 slices over axes of degree 2/4: no axis absorbs 3 — warn and
    keep enumeration order rather than fail bring-up."""
    devs = []
    for s in range(3):
      for i in range(2):
        devs.append(_FakeTPU(s * 2 + i, (i, 0, 0), slice_index=s))
    import logging
    with caplog.at_level(logging.WARNING,
                         logger="tensorflowonspark_tpu.parallel.mesh"):
      mesh = M.build_mesh(M.MeshSpec(data=2, tensor=3), devices=devs)
    assert "falling back to enumeration order" in caplog.text
    assert list(np.asarray(mesh.devices).ravel()) == devs


class TestCollectives:
  def test_psum_and_ring_permute(self, devices):
    mesh = M.build_mesh(M.MeshSpec(data=8), devices=devices)

    def body(x):
      total = C.all_reduce(jnp.sum(x), M.AXIS_DATA)
      rotated = C.ring_permute(x, M.AXIS_DATA, shift=1)
      return total * jnp.ones_like(x), rotated

    x = jnp.arange(16.0)
    fn = C.shard_map_fn(body, mesh, in_specs=P(M.AXIS_DATA),
                        out_specs=(P(M.AXIS_DATA), P(M.AXIS_DATA)))
    total, rotated = jax.jit(fn)(x)
    assert float(total[0]) == float(x.sum())
    # shard i moves to slot i+1: slot 0 now holds the last shard
    np.testing.assert_allclose(np.asarray(rotated[:2]), [14.0, 15.0])

  def test_hierarchical_all_reduce_matches_psum(self, devices):
    """reduce_scatter(ICI) → psum(DCN) → all_gather(ICI) must equal the
    flat psum over both axes (and the mean variant the pmean)."""
    mesh = M.build_mesh(M.MeshSpec(data=2, fsdp=4), devices=devices)
    # 8 dim-0 shards of 4 rows each: the ICI reduce_scatter needs the local
    # shard's scatter dim divisible by the fsdp axis size (4)
    x = jnp.arange(256.0).reshape(32, 8)

    def flat(v):
      return lax.psum(v, (M.AXIS_FSDP, M.AXIS_DATA))

    def tiered(v):
      return C.hierarchical_all_reduce(v, ici_axis=M.AXIS_FSDP,
                                       dcn_axis=M.AXIS_DATA)

    spec = P((M.AXIS_DATA, M.AXIS_FSDP))
    got_flat = jax.jit(C.shard_map_fn(flat, mesh, spec, spec))(x)
    got_tier = jax.jit(C.shard_map_fn(tiered, mesh, spec, spec))(x)
    np.testing.assert_allclose(np.asarray(got_tier), np.asarray(got_flat),
                               rtol=1e-6)
    mean = jax.jit(C.shard_map_fn(
        lambda v: C.hierarchical_all_reduce(v, M.AXIS_FSDP, M.AXIS_DATA,
                                            mean=True), mesh, spec, spec))(x)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(got_flat) / 8,
                               rtol=1e-6)

  def test_sync_gradients_averages_pytree(self, devices):
    mesh = M.build_mesh(M.MeshSpec(data=8), devices=devices)
    grads = {"w": jnp.arange(8.0), "b": jnp.ones((8, 2))}

    def body(g):
      return C.sync_gradients(g, M.AXIS_DATA)

    spec = {"w": P(M.AXIS_DATA), "b": P(M.AXIS_DATA)}
    out = jax.jit(C.shard_map_fn(body, mesh, (spec,), spec))(grads)
    # every shard of w becomes the mean of the 8 single-element shards
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.full(8, np.arange(8.0).mean()))
    np.testing.assert_allclose(np.asarray(out["b"]), np.ones((8, 2)))

  def test_broadcast_from(self, devices):
    mesh = M.build_mesh(M.MeshSpec(data=8), devices=devices)
    x = jnp.arange(8.0)
    out = jax.jit(C.shard_map_fn(
        lambda v: C.broadcast_from(v, M.AXIS_DATA, src_index=3),
        mesh, P(M.AXIS_DATA), P(M.AXIS_DATA)))(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 3.0))

  def test_global_norm_cross_shard(self, devices):
    mesh = M.build_mesh(M.MeshSpec(data=8), devices=devices)
    tree = {"a": jnp.arange(8.0), "b": -jnp.arange(16.0).reshape(8, 2)}
    expected = float(jnp.sqrt(sum(jnp.sum(v * v)
                                  for v in tree.values())))
    spec = {"a": P(M.AXIS_DATA), "b": P(M.AXIS_DATA)}
    out = jax.jit(C.shard_map_fn(
        lambda t: C.global_norm(t, M.AXIS_DATA) * jnp.ones(1),
        mesh, (spec,), P()))(tree)
    np.testing.assert_allclose(float(out[0]), expected, rtol=1e-6)

  def test_clip_by_global_norm(self, devices):
    mesh = M.build_mesh(M.MeshSpec(data=8), devices=devices)
    tree = {"g": jnp.full(8, 3.0)}   # global norm = sqrt(8*9) ~ 8.49
    spec = {"g": P(M.AXIS_DATA)}

    def body(t):
      clipped, norm = C.clip_by_global_norm(t, 1.0, M.AXIS_DATA)
      return clipped, norm * jnp.ones(1)

    clipped, norm = jax.jit(C.shard_map_fn(
        body, mesh, (spec,), (spec, P())))(tree)
    np.testing.assert_allclose(float(norm[0]), float(np.sqrt(72)), rtol=1e-6)
    # clipped global norm is exactly max_norm
    np.testing.assert_allclose(
        float(np.sqrt((np.asarray(clipped["g"]) ** 2).sum())), 1.0,
        rtol=1e-5)


class TestRingAttention:
  @pytest.mark.parametrize("causal", [True, False])
  def test_matches_full_attention(self, devices, causal):
    mesh = M.build_mesh(M.MeshSpec(data=2, sequence=4), devices=devices)
    rng = np.random.RandomState(0)
    B, S, H, D = 2, 32, 4, 16
    q, k, v = (jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
               for _ in range(3))
    ref = RA.full_attention(q, k, v, causal=causal)
    out = jax.jit(
        lambda q, k, v: RA.ring_attention(q, k, v, mesh, causal=causal)
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)

  @pytest.mark.parametrize("causal", [True, False])
  def test_ring_flash_matches_full_attention(self, devices, causal):
    """Ring attention with Pallas flash blocks: forward exactness."""
    mesh = M.build_mesh(M.MeshSpec(data=2, sequence=4), devices=devices)
    rng = np.random.RandomState(1)
    B, S, H, D = 2, 32, 2, 16
    q, k, v = (jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
               for _ in range(3))
    ref = RA.full_attention(q, k, v, causal=causal)
    out = jax.jit(lambda q, k, v: RA.ring_attention(
        q, k, v, mesh, causal=causal, use_flash=True, blk_q=8, blk_k=8,
        interpret=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)

  def _expand(self, kv, h):
    return np.repeat(np.asarray(kv), h // kv.shape[2], axis=2)

  @pytest.mark.parametrize("use_flash", [False, True])
  def test_gqa_grouped_kv_matches_expanded(self, devices, use_flash):
    """GQA ring: grouped K/V (hk < h) gives exactly the attention of the
    expanded equivalent — the ring expands per step, locally."""
    mesh = M.build_mesh(M.MeshSpec(sequence=4), devices=devices[:4])
    rng = np.random.RandomState(5)
    B, S, H, HK, D = 2, 32, 4, 2, 16
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, HK, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, HK, D), jnp.float32)
    ref = RA.full_attention(q, jnp.asarray(self._expand(k, H)),
                            jnp.asarray(self._expand(v, H)), causal=True)
    kwargs = dict(use_flash=True, blk_q=8, blk_k=8, interpret=True) \
        if use_flash else {}
    out = jax.jit(lambda q, k, v: RA.ring_attention(
        q, k, v, mesh, causal=True, **kwargs))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)

  def test_gqa_grads_match_expanded_dense(self, devices):
    mesh = M.build_mesh(M.MeshSpec(sequence=4), devices=devices[:4])
    rng = np.random.RandomState(6)
    B, S, H, HK, D = 1, 32, 4, 2, 8
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, HK, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, HK, D), jnp.float32)
    w = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)

    def loss_ring(q, k, v):
      return jnp.sum(RA.ring_attention(q, k, v, mesh, causal=True) * w)

    def loss_dense(q, k, v):
      ke = jnp.repeat(k, H // HK, axis=2)
      ve = jnp.repeat(v, H // HK, axis=2)
      return jnp.sum(RA.full_attention(q, ke, ve, causal=True) * w)

    gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gd):
      np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                 atol=1e-4, rtol=1e-4)

  def test_gqa_indivisible_tensor_axis_expands_up_front(self, devices):
    """When a tensor axis shards heads and cannot divide the grouped KV
    count (hk=2 on tensor=4), the ring expands KV up front rather than
    break the head spec — correctness preserved at pre-GQA traffic."""
    mesh = M.build_mesh(M.MeshSpec(sequence=2, tensor=4),
                        devices=devices[:8])
    rng = np.random.RandomState(8)
    B, S, H, HK, D = 1, 16, 4, 2, 8
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, HK, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, HK, D), jnp.float32)
    ref = RA.full_attention(q, jnp.asarray(self._expand(k, H)),
                            jnp.asarray(self._expand(v, H)), causal=True)
    out = jax.jit(lambda q, k, v: RA.ring_attention(
        q, k, v, mesh, causal=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)

  def test_gqa_ring_permutes_grouped_blocks(self, devices):
    """Structural ICI-traffic check: every ppermute in the ring program
    carries HK (grouped) heads, never the expanded H."""
    mesh = M.build_mesh(M.MeshSpec(sequence=4), devices=devices[:4])
    B, S, H, HK, D = 1, 32, 4, 2, 8
    q = jnp.zeros((B, S, H, D), jnp.float32)
    k = jnp.zeros((B, S, HK, D), jnp.float32)
    v = jnp.zeros((B, S, HK, D), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q, k, v: RA.ring_attention(
        q, k, v, mesh, causal=True))(q, k, v)

    shapes = []

    def walk(jx):
      for eqn in jx.eqns:
        if eqn.primitive.name == "ppermute":
          shapes.append(tuple(eqn.invars[0].aval.shape))
        for val in eqn.params.values():
          for sub in jax.tree.leaves(val, is_leaf=lambda x: hasattr(x, "eqns")):
            if hasattr(sub, "eqns"):
              walk(sub)
            elif hasattr(sub, "jaxpr"):
              walk(sub.jaxpr)

    walk(jaxpr.jaxpr)
    assert shapes, "no ppermute found in the ring program"
    for shp in shapes:
      assert shp[2] == HK, "ring permuted expanded heads: %r" % (shp,)

  def test_gqa_ring_flash_grads_match_expanded(self, devices):
    """GQA ring on the FLASH path: grouped KV flows unexpanded into the
    kernels (grouped-aware BlockSpec + cross-head dK/dV accumulation) and
    grads still equal AD through the expanded dense reference — the
    round-3 ROADMAP deferral, closed."""
    mesh = M.build_mesh(M.MeshSpec(sequence=4), devices=devices[:4])
    rng = np.random.RandomState(9)
    B, S, H, HK, D = 1, 32, 4, 2, 8
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, HK, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, HK, D), jnp.float32)
    w = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)

    def loss_ring(q, k, v):
      return jnp.sum(RA.ring_attention(q, k, v, mesh, causal=True,
                                       use_flash=True, blk_q=8, blk_k=8,
                                       interpret=True) * w)

    def loss_dense(q, k, v):
      ke = jnp.repeat(k, H // HK, axis=2)
      ve = jnp.repeat(v, H // HK, axis=2)
      return jnp.sum(RA.full_attention(q, ke, ve, causal=True) * w)

    gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gd):
      np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                 atol=1e-4, rtol=1e-4)

  def test_ring_flash_gradients_match_dense(self, devices):
    """Training through ring-flash: grads equal dense full attention."""
    mesh = M.build_mesh(M.MeshSpec(sequence=4), devices=devices[:4])
    rng = np.random.RandomState(2)
    B, S, H, D = 1, 32, 2, 8
    q, k, v = (jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
               for _ in range(3))
    w = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)

    def loss_ring(q, k, v):
      return jnp.sum(w * RA.ring_attention(
          q, k, v, mesh, causal=True, use_flash=True, blk_q=8, blk_k=8,
          interpret=True))

    def loss_dense(q, k, v):
      return jnp.sum(w * RA.full_attention(q, k, v, causal=True))

    gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gd):
      np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                 atol=1e-4, rtol=1e-4)


class TestPipelineParallel:
  def test_matches_sequential(self, devices):
    from tensorflowonspark_tpu.parallel import pipeline_parallel as PP

    mesh = M.build_mesh(M.MeshSpec(data=2, pipeline=4), devices=devices)
    rng = np.random.RandomState(0)
    n_stages, d = 4, 16
    # stage i: x -> tanh(x @ W_i)
    W = jnp.asarray(rng.randn(n_stages, d, d) * 0.3, jnp.float32)
    x = jnp.asarray(rng.randn(8, d), jnp.float32)

    def stage_fn(w, a):
      return jnp.tanh(a @ w)

    ref = x
    for i in range(n_stages):
      ref = stage_fn(W[i], ref)

    out = jax.jit(lambda W, x: PP.pipeline_apply(
        stage_fn, W, x, mesh, num_microbatches=4))(W, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)

  def test_differentiable(self, devices):
    from tensorflowonspark_tpu.parallel import pipeline_parallel as PP

    mesh = M.build_mesh(M.MeshSpec(pipeline=4), devices=devices[:4])
    rng = np.random.RandomState(1)
    W = jnp.asarray(rng.randn(4, 8, 8) * 0.3, jnp.float32)
    x = jnp.asarray(rng.randn(4, 8), jnp.float32)

    def stage_fn(w, a):
      return jnp.tanh(a @ w)

    def loss_pipe(W):
      return jnp.sum(PP.pipeline_apply(stage_fn, W, x, mesh, 2) ** 2)

    def loss_seq(W):
      a = x
      for i in range(4):
        a = stage_fn(W[i], a)
      return jnp.sum(a ** 2)

    g_pipe = jax.jit(jax.grad(loss_pipe))(W)
    g_seq = jax.grad(loss_seq)(W)
    np.testing.assert_allclose(np.asarray(g_pipe), np.asarray(g_seq),
                               atol=1e-4, rtol=1e-4)


class TestPipeline1F1B:
  """The 1F1B schedule: loss+grads in one interleaved loop with an
  O(n_stages) activation ring — must agree with plain sequential AD."""

  def _setup(self):
    from tensorflowonspark_tpu.parallel import pipeline_parallel as PP
    rng = np.random.RandomState(7)
    n_stages, d, b = 4, 16, 8
    W = jnp.asarray(rng.randn(n_stages, d, d) * 0.3, jnp.float32)
    x = jnp.asarray(rng.randn(b, d), jnp.float32)
    t = jnp.asarray(rng.randn(b, d), jnp.float32)

    def stage_fn(w, a):
      return jnp.tanh(a @ w)

    def loss_fn(y, tgt):
      return jnp.mean((y - tgt) ** 2)

    def seq_loss(W):
      a = x
      for i in range(n_stages):
        a = stage_fn(W[i], a)
      return loss_fn(a, t)

    return PP, stage_fn, loss_fn, W, x, t, seq_loss

  @pytest.mark.parametrize("n_micro", [2, 4, 8])
  def test_matches_sequential_grads(self, devices, n_micro):
    PP, stage_fn, loss_fn, W, x, t, seq_loss = self._setup()
    mesh = M.build_mesh(M.MeshSpec(pipeline=4), devices=devices[:4])
    loss, grads = jax.jit(lambda W, x, t: PP.pipeline_train_step(
        stage_fn, loss_fn, W, x, t, mesh, num_microbatches=n_micro))(W, x, t)
    np.testing.assert_allclose(float(loss), float(seq_loss(W)),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grads),
                               np.asarray(jax.grad(seq_loss)(W)),
                               atol=1e-4, rtol=1e-4)

  def test_cond_is_real_branch(self, devices):
    """The stage-0 embed and last-stage head+loss are guarded by lax.cond
    on the pipeline axis index. Under vmap such conds lower to select
    (both branches run everywhere — a silent perf regression); under
    shard_map the predicate is a per-device scalar and must survive as a
    real HLO ``conditional`` (round-3 advice)."""
    PP, stage_fn, loss_fn, W, x, t, _ = self._setup()
    mesh = M.build_mesh(M.MeshSpec(pipeline=4), devices=devices[:4])
    f = jax.jit(lambda W, x, t: PP.pipeline_train_step(
        stage_fn, loss_fn, W, x, t, mesh, num_microbatches=4))
    hlo = f.lower(W, x, t).compile().as_text()
    assert "conditional(" in hlo, \
        "embed/head lax.cond was lowered to select: edge-stage work " \
        "now runs on every stage"

  def test_bf16_params_and_loss(self, devices):
    """bf16 end-to-end: the loss-vjp cotangent matches the loss dtype and
    grads accumulate in f32 before casting back to the param dtype."""
    PP, stage_fn, loss_fn, W, x, t, seq_loss = self._setup()
    Wb = W.astype(jnp.bfloat16)
    xb, tb = x.astype(jnp.bfloat16), t.astype(jnp.bfloat16)
    mesh = M.build_mesh(M.MeshSpec(pipeline=4), devices=devices[:4])
    loss, grads = jax.jit(lambda W, x, t: PP.pipeline_train_step(
        stage_fn, loss_fn, W, x, t, mesh, num_microbatches=4))(Wb, xb, tb)
    assert jax.tree.leaves(grads)[0].dtype == jnp.bfloat16
    np.testing.assert_allclose(float(loss), float(seq_loss(W)), atol=0.05)
    np.testing.assert_allclose(np.asarray(grads, np.float32),
                               np.asarray(jax.grad(seq_loss)(W)),
                               atol=0.05)

  def test_inputs_scattered_along_pipeline(self, devices):
    """With n_micro % S == 0 the token/target microbatches are scattered
    over the pipeline axis and ride ppermute conveyors (round-4 verdict
    item 6) — visible as extra collective-permutes in the compiled HLO
    versus the replicated fallback (n_micro < S). A silent regression to
    always-replicate would pass the parity tests; this pins the path."""
    PP, stage_fn, loss_fn, W, x, t, _ = self._setup()
    mesh = M.build_mesh(M.MeshSpec(pipeline=4), devices=devices[:4])

    def cp_count(n_micro):
      hlo = jax.jit(lambda W, x, t: PP.pipeline_train_step(
          stage_fn, loss_fn, W, x, t, mesh,
          num_microbatches=n_micro)).lower(W, x, t).compile().as_text()
      return hlo.count("collective-permute(")

    replicated = cp_count(2)    # 2 < S=4 -> fallback: act + cotangent CPs
    scattered = cp_count(4)     # divisible -> + token & target conveyors
    assert replicated >= 2
    assert scattered > replicated, (replicated, scattered)

  def test_microbatch_data_divisibility_asserts(self, devices):
    PP, stage_fn, loss_fn, W, x, t, _ = self._setup()
    mesh = M.build_mesh(M.MeshSpec(data=2, pipeline=4), devices=devices)
    with pytest.raises(AssertionError, match="data-axis extent"):
      PP.pipeline_train_step(stage_fn, loss_fn, W, x, t, mesh,
                             num_microbatches=8)  # micro_b=1, data=2

  def test_with_data_parallel_axis(self, devices):
    """DP x PP: per-shard losses/grads pmean over the data axis so the
    result equals the global-batch computation."""
    PP, stage_fn, loss_fn, W, x, t, seq_loss = self._setup()
    mesh = M.build_mesh(M.MeshSpec(data=2, pipeline=4), devices=devices)
    loss, grads = jax.jit(lambda W, x, t: PP.pipeline_train_step(
        stage_fn, loss_fn, W, x, t, mesh, num_microbatches=4))(W, x, t)
    np.testing.assert_allclose(float(loss), float(seq_loss(W)),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grads),
                               np.asarray(jax.grad(seq_loss)(W)),
                               atol=1e-4, rtol=1e-4)


class TestExpertParallel:
  def test_matches_reference(self, devices):
    from tensorflowonspark_tpu.parallel import expert_parallel as EP

    mesh = M.build_mesh(M.MeshSpec(data=2, expert=4), devices=devices)
    params = EP.init_moe_params(jax.random.PRNGKey(0), num_experts=8,
                                d_model=16, d_ff=32)
    x = jnp.asarray(np.random.RandomState(0).randn(24, 16), jnp.float32)
    ref = EP.moe_ffn_reference(params, x)
    sharded = EP.shard_moe_params(params, mesh)
    out = jax.jit(lambda p, x: EP.moe_ffn(p, x, mesh))(sharded, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)

  def test_expert_weights_actually_sharded(self, devices):
    from tensorflowonspark_tpu.parallel import expert_parallel as EP
    mesh = M.build_mesh(M.MeshSpec(expert=8), devices=devices)
    params = EP.shard_moe_params(
        EP.init_moe_params(jax.random.PRNGKey(0), 8, 16, 32), mesh)
    assert len(params["w_up"].sharding.device_set) == 8

  def test_a2a_matches_reference_with_ample_capacity(self, devices):
    from tensorflowonspark_tpu.parallel import expert_parallel as EP

    mesh = M.build_mesh(M.MeshSpec(data=2, expert=4), devices=devices)
    params = EP.init_moe_params(jax.random.PRNGKey(0), num_experts=8,
                                d_model=16, d_ff=32)
    x = jnp.asarray(np.random.RandomState(0).randn(64, 16), jnp.float32)
    ref = EP.moe_ffn_reference(params, x)
    sharded = EP.shard_moe_params(params, mesh)
    # capacity_factor high enough that no token is dropped
    out = jax.jit(lambda p, x: EP.moe_ffn_a2a(p, x, mesh,
                                              capacity_factor=8.0))(
        sharded, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)

  def test_a2a_capacity_drops_gracefully(self, devices):
    from tensorflowonspark_tpu.parallel import expert_parallel as EP
    mesh = M.build_mesh(M.MeshSpec(expert=4), devices=devices[:4])
    params = EP.init_moe_params(jax.random.PRNGKey(0), 4, 8, 16)
    x = jnp.asarray(np.random.RandomState(1).randn(32, 8), jnp.float32)
    sharded = EP.shard_moe_params(params, mesh)
    # tiny capacity: result must be finite (dropped tokens -> zeros)
    out = jax.jit(lambda p, x: EP.moe_ffn_a2a(p, x, mesh,
                                              capacity_factor=0.5))(
        sharded, x)
    assert np.isfinite(np.asarray(out)).all()

  def test_a2a_top2_matches_reference_with_ample_capacity(self, devices):
    from tensorflowonspark_tpu.parallel import expert_parallel as EP

    mesh = M.build_mesh(M.MeshSpec(data=2, expert=4), devices=devices)
    params = EP.init_moe_params(jax.random.PRNGKey(4), num_experts=8,
                                d_model=16, d_ff=32)
    x = jnp.asarray(np.random.RandomState(4).randn(64, 16), jnp.float32)
    ref = EP.moe_ffn_reference(params, x, top_k=2)
    sharded = EP.shard_moe_params(params, mesh)
    out = jax.jit(lambda p, x: EP.moe_ffn_a2a(p, x, mesh,
                                              capacity_factor=8.0,
                                              top_k=2))(sharded, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)

  def test_a2a_top2_capacity_drops_only_overflow(self, devices):
    """With a tight capacity, surviving assignments keep their renormalized
    weights — outputs stay finite and within the ample-capacity envelope."""
    from tensorflowonspark_tpu.parallel import expert_parallel as EP
    mesh = M.build_mesh(M.MeshSpec(expert=4), devices=devices[:4])
    params = EP.init_moe_params(jax.random.PRNGKey(5), 4, 8, 16)
    x = jnp.asarray(np.random.RandomState(5).randn(32, 8), jnp.float32)
    sharded = EP.shard_moe_params(params, mesh)
    tight = jax.jit(lambda p, x: EP.moe_ffn_a2a(
        p, x, mesh, capacity_factor=0.5, top_k=2))(sharded, x)
    ample = jax.jit(lambda p, x: EP.moe_ffn_a2a(
        p, x, mesh, capacity_factor=8.0, top_k=2))(sharded, x)
    assert np.isfinite(np.asarray(tight)).all()
    per_token = jnp.abs(tight - ample).max(axis=-1)
    assert float(per_token.max()) > 1e-6      # something was dropped
    # early queue positions fit under even a tight capacity, so some
    # tokens' outputs must survive exactly
    assert int((per_token < 1e-6).sum()) >= 1

  def test_top2_routing_matches_reference(self, devices):
    from tensorflowonspark_tpu.parallel import expert_parallel as EP
    mesh = M.build_mesh(M.MeshSpec(data=2, expert=4), devices=devices)
    params = EP.init_moe_params(jax.random.PRNGKey(2), 8, 16, 32)
    x = jnp.asarray(np.random.RandomState(2).randn(24, 16), jnp.float32)
    ref = EP.moe_ffn_reference(params, x, top_k=2)
    out = jax.jit(lambda p, x: EP.moe_ffn(p, x, mesh, top_k=2))(
        EP.shard_moe_params(params, mesh), x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    # top-2 combine weights sum to 1 per token -> output differs from top-1
    top1 = EP.moe_ffn_reference(params, x, top_k=1)
    assert float(jnp.max(jnp.abs(top1 - ref))) > 1e-4

  def test_load_balancing_loss(self):
    from tensorflowonspark_tpu.parallel import expert_parallel as EP
    params = EP.init_moe_params(jax.random.PRNGKey(0), 4, 8, 16)
    x = jnp.asarray(np.random.RandomState(3).randn(256, 8), jnp.float32)
    aux = float(EP.load_balancing_loss(params, x))
    assert aux >= 1.0 - 1e-3          # 1.0 is the uniform-routing floor
    assert np.isfinite(aux)
    # differentiable w.r.t. the gate
    g = jax.grad(lambda p: EP.load_balancing_loss(p, x))(params)
    assert float(jnp.abs(g["w_gate"]).sum()) > 0

  def test_differentiable(self, devices):
    from tensorflowonspark_tpu.parallel import expert_parallel as EP
    mesh = M.build_mesh(M.MeshSpec(expert=4), devices=devices[:4])
    params = EP.init_moe_params(jax.random.PRNGKey(1), 4, 8, 16)
    x = jnp.asarray(np.random.RandomState(1).randn(6, 8), jnp.float32)

    g_ref = jax.grad(lambda p: jnp.sum(
        EP.moe_ffn_reference(p, x) ** 2))(params)
    sharded = EP.shard_moe_params(params, mesh)
    g_shard = jax.jit(jax.grad(lambda p: jnp.sum(
        EP.moe_ffn(p, x, mesh) ** 2)))(sharded)
    np.testing.assert_allclose(np.asarray(g_shard["w_up"]),
                               np.asarray(g_ref["w_up"]),
                               atol=1e-4, rtol=1e-4)


class TestShardedTrainStep:
  def test_gqa_kv_heads_replicate_when_indivisible(self, devices):
    """GQA K/V projections whose head count the tensor axis can't divide
    fall back to replication instead of failing state init (kv_heads=2
    on tensor=4); the model still initializes sharded and takes a step."""
    from tensorflowonspark_tpu.models import transformer as tfm

    mesh = M.build_mesh(M.MeshSpec(data=2, tensor=4), devices=devices)
    cfg = tfm.TransformerConfig(vocab_size=32, num_layers=2, num_heads=8,
                                num_kv_heads=2, d_model=32, d_ff=64,
                                max_seq_len=16, remat=False,
                                dtype=jnp.float32)
    state, sharding = tfm.create_sharded_state(jax.random.PRNGKey(0), cfg,
                                               mesh, seq_len=16)

    def loss_fn(params, tokens):
      return tfm.causal_lm_loss(
          state.apply_fn({"params": params}, tokens), tokens)

    step = SH.make_train_step(loss_fn, mesh, sharding)
    rng = np.random.RandomState(0)
    tokens = SH.shard_batch(
        jnp.asarray(rng.randint(0, 32, (4, 16)), jnp.int32), mesh)
    state, loss = step(state, tokens)
    assert np.isfinite(float(loss))

  def test_transformer_trains_sharded(self, devices):
    """Full dp+sp+tp train loop: loss must decrease on a tiny corpus."""
    from tensorflowonspark_tpu.models import transformer as tfm

    mesh = M.build_mesh(M.MeshSpec(data=2, sequence=2, tensor=2),
                        devices=devices)
    seq = 32
    cfg = tfm.TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                                d_model=64, d_ff=128, max_seq_len=seq,
                                remat=False, use_ring_attention=True)
    state, sharding = tfm.create_sharded_state(jax.random.PRNGKey(0), cfg,
                                               mesh, learning_rate=1e-2,
                                               seq_len=seq)

    def loss_fn(params, tokens):
      return tfm.causal_lm_loss(
          state.apply_fn({"params": params}, tokens), tokens)

    step = SH.make_train_step(loss_fn, mesh, sharding,
                              batch_extra_axes=(M.AXIS_SEQUENCE,))
    # a learnable pattern: token ids follow a fixed cycle
    base = np.tile(np.arange(seq) % 16, (4, 1)).astype("int32")
    tokens = SH.shard_batch(jnp.asarray(base), mesh,
                            extra_axes=(M.AXIS_SEQUENCE,))
    losses = []
    for _ in range(8):
      state, loss = step(state, tokens)
      losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, losses

    # params actually sharded: at least one leaf spans multiple devices
    leaves = jax.tree.leaves(state.params)
    assert any(len(l.sharding.device_set) > 1 for l in leaves)

  def test_state_shardings_distinguish_same_shape_params(self, devices):
    """Adam moments must mirror THEIR parameter's layout: two params with
    identical shapes but different shardings each keep their own (a
    shape-keyed lookup would assign both the first layout and silently
    reshard between a param and its moments every step)."""
    import optax
    from flax.training import train_state
    from jax.sharding import NamedSharding

    mesh = M.build_mesh(M.MeshSpec(data=2, tensor=2), devices=devices[:4])
    params = {"a": jnp.zeros((8, 8)), "b": jnp.zeros((8, 8))}
    abs_state = jax.eval_shape(lambda: train_state.TrainState.create(
        apply_fn=lambda v, x: x, params=params, tx=optax.adam(1e-3)))
    sh_a = NamedSharding(mesh, P(M.AXIS_TENSOR, None))
    sh_b = NamedSharding(mesh, P(None, M.AXIS_TENSOR))
    full = SH.state_shardings(abs_state, {"a": sh_a, "b": sh_b}, mesh)
    mu = full.opt_state[0].mu
    nu = full.opt_state[0].nu
    assert mu["a"] == sh_a and nu["a"] == sh_a
    assert mu["b"] == sh_b and nu["b"] == sh_b

  def test_fused_layer_norm_matches_flax_in_model(self, devices):
    """The fused Pallas LayerNorm (per-shard via shard_map) trains the
    sharded transformer on the same trajectory as flax LayerNorm."""
    from tensorflowonspark_tpu.models import transformer as tfm

    mesh = M.build_mesh(M.MeshSpec(data=2, sequence=2, tensor=2),
                        devices=devices)
    seq = 32
    losses = {}
    for impl in ("flax", "fused"):
      cfg = tfm.TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                                  d_model=64, d_ff=128, max_seq_len=seq,
                                  remat=False, dtype=jnp.float32,
                                  use_ring_attention=True,
                                  layer_norm_impl=impl)
      state, sharding = tfm.create_sharded_state(jax.random.PRNGKey(0), cfg,
                                                 mesh, learning_rate=1e-2,
                                                 seq_len=seq)
      if impl == "fused":   # the fused module actually in the tree
        assert "scale" in state.params["layer_0"]["ln1"]

      def loss_fn(params, tokens, apply_fn=state.apply_fn):
        return tfm.causal_lm_loss(apply_fn({"params": params}, tokens),
                                  tokens)

      step = SH.make_train_step(loss_fn, mesh, sharding,
                                batch_extra_axes=(M.AXIS_SEQUENCE,))
      base = np.tile(np.arange(seq) % 16, (4, 1)).astype("int32")
      tokens = SH.shard_batch(jnp.asarray(base), mesh,
                              extra_axes=(M.AXIS_SEQUENCE,))
      traj = []
      for _ in range(4):
        state, loss = step(state, tokens)
        traj.append(float(loss))
      losses[impl] = traj
    np.testing.assert_allclose(losses["fused"], losses["flax"],
                               atol=1e-5, rtol=1e-5)

  def test_ring_flash_in_model_matches_dense(self, devices):
    """Sequence-parallel training with the flash kernels forced inside the
    ring (attention_impl="flash") follows the dense trajectory — the
    production long-context path, exercised via interpret mode on CPU."""
    from tensorflowonspark_tpu.models import transformer as tfm

    mesh = M.build_mesh(M.MeshSpec(data=2, sequence=2), devices=devices[:4])
    seq = 32
    losses = {}
    for impl in ("dense", "flash"):
      cfg = tfm.TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                                  d_model=64, d_ff=128, max_seq_len=seq,
                                  remat=False, dtype=jnp.float32,
                                  use_ring_attention=True,
                                  attention_impl=impl)
      state, sharding = tfm.create_sharded_state(jax.random.PRNGKey(0), cfg,
                                                 mesh, learning_rate=1e-2,
                                                 seq_len=seq)

      def loss_fn(params, tokens, apply_fn=state.apply_fn):
        return tfm.causal_lm_loss(apply_fn({"params": params}, tokens),
                                  tokens)

      step = SH.make_train_step(loss_fn, mesh, sharding,
                                batch_extra_axes=(M.AXIS_SEQUENCE,))
      base = np.tile(np.arange(seq) % 16, (4, 1)).astype("int32")
      tokens = SH.shard_batch(jnp.asarray(base), mesh,
                              extra_axes=(M.AXIS_SEQUENCE,))
      traj = []
      for _ in range(4):
        state, loss = step(state, tokens)
        traj.append(float(loss))
      losses[impl] = traj
    np.testing.assert_allclose(losses["flash"], losses["dense"],
                               atol=2e-4, rtol=2e-4)

  def test_moe_transformer_sharded_over_expert_axis(self, devices):
    """The MoE flagship trains with experts sharded over the expert axis
    inside one jitted SPMD step."""
    from tensorflowonspark_tpu.models import transformer as tfm

    mesh = M.build_mesh(M.MeshSpec(data=2, expert=4), devices=devices)
    cfg = tfm.TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                                d_model=64, d_ff=128, remat=False,
                                dtype=jnp.float32, moe_experts=4,
                                moe_top_k=2, moe_every=2)
    state, sharding = tfm.create_sharded_state(jax.random.PRNGKey(0), cfg,
                                               mesh, learning_rate=1e-2,
                                               seq_len=16)
    w_up = state.params["layer_1"]["moe"]["w_up"]
    assert len(w_up.sharding.device_set) >= 4   # experts actually sharded

    def loss_fn(params, tokens):
      return tfm.causal_lm_loss(
          state.apply_fn({"params": params}, tokens), tokens)

    step = SH.make_train_step(loss_fn, mesh, sharding)
    base = np.tile(np.arange(16) % 8, (8, 1)).astype("int32")
    tokens = SH.shard_batch(jnp.asarray(base), mesh)
    losses = []
    for _ in range(8):
      state, loss = step(state, tokens)
      losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8, losses

  def test_moe_transformer_a2a_dispatch_path(self, devices):
    """moe_capacity_factor > 0 routes MoE layers through the GShard
    all-to-all dispatch inside the jitted SPMD step; training still
    converges on the cyclic-token corpus."""
    from tensorflowonspark_tpu.models import transformer as tfm

    mesh = M.build_mesh(M.MeshSpec(data=2, expert=4), devices=devices)
    cfg = tfm.TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                                d_model=64, d_ff=128, remat=False,
                                dtype=jnp.float32, moe_experts=4,
                                moe_top_k=2, moe_every=2,
                                moe_capacity_factor=4.0)
    state, sharding = tfm.create_sharded_state(jax.random.PRNGKey(0), cfg,
                                               mesh, learning_rate=1e-2,
                                               seq_len=16)

    def loss_fn(params, tokens):
      return tfm.causal_lm_loss(
          state.apply_fn({"params": params}, tokens), tokens)

    step = SH.make_train_step(loss_fn, mesh, sharding)
    base = np.tile(np.arange(16) % 8, (8, 1)).astype("int32")
    tokens = SH.shard_batch(jnp.asarray(base), mesh)
    losses = []
    for _ in range(8):
      state, loss = step(state, tokens)
      losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8, losses

  def test_param_shardings_follow_rules(self, devices):
    from tensorflowonspark_tpu.models import transformer as tfm

    mesh = M.build_mesh(M.MeshSpec(data=2, tensor=4), devices=devices)
    cfg = tfm.TransformerConfig(vocab_size=64, num_layers=1, num_heads=4,
                                d_model=64, d_ff=128, remat=False)
    state, _ = tfm.create_sharded_state(jax.random.PRNGKey(0), cfg, mesh,
                                        seq_len=16)
    up = state.params["layer_0"]["mlp"]["up"]["kernel"]
    # mlp dim sharded over 4-way tensor axis
    assert up.sharding.spec[-1] == M.AXIS_TENSOR


class TestRingGQATransformer:
  def test_ring_gqa_logits_match_dense(self, devices):
    """The model's ring path feeds GROUPED K/V into the ring (ICI traffic
    cut by num_heads/kv_heads); logits must equal the mesh-free dense
    path on identical params."""
    from tensorflowonspark_tpu.models import transformer as tfm

    mesh = M.build_mesh(M.MeshSpec(sequence=4), devices=devices[:4])
    cfg = tfm.TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                                num_kv_heads=2, d_model=64, d_ff=128,
                                max_seq_len=32, remat=False,
                                dtype=jnp.float32, use_ring_attention=True)
    state = tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=32)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 64, (2, 32)), jnp.int32)

    ring_logits = jax.jit(lambda p, t: tfm.Transformer(cfg, mesh).apply(
        {"params": p}, t))(state.params, tokens)
    import dataclasses
    cfg_d = dataclasses.replace(cfg, use_ring_attention=False)
    dense_logits = tfm.Transformer(cfg_d, None).apply(
        {"params": state.params}, tokens)
    np.testing.assert_allclose(np.asarray(ring_logits),
                               np.asarray(dense_logits),
                               atol=1e-4, rtol=1e-4)


class TestRingWindow:
  """Sliding-window attention through the ring (sequence parallelism):
  both ring paths (dense blocks and Pallas flash blocks) must match the
  dense windowed reference, including windows that straddle shard
  boundaries and windows smaller than one shard."""

  @pytest.mark.parametrize("use_flash", [False, True])
  @pytest.mark.parametrize("window", [3, 8, 20])
  def test_ring_window_matches_dense(self, devices, use_flash, window):
    mesh = M.build_mesh(M.MeshSpec(data=2, sequence=4), devices=devices)
    rng = np.random.RandomState(2)
    B, S, H, D = 2, 32, 2, 16
    q, k, v = (jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
               for _ in range(3))
    ref = RA.full_attention(q, k, v, causal=True, window=window)
    out = jax.jit(lambda q, k, v: RA.ring_attention(
        q, k, v, mesh, causal=True, use_flash=use_flash, blk_q=8, blk_k=8,
        interpret=True, window=window))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)

  def test_ring_window_grads_match_dense(self, devices):
    mesh = M.build_mesh(M.MeshSpec(sequence=4), devices=devices[:4])
    rng = np.random.RandomState(3)
    B, S, H, D = 1, 32, 2, 8
    q, k, v = (jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
               for _ in range(3))
    w = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)

    def ring_loss(q, k, v):
      return jnp.sum(w * RA.ring_attention(q, k, v, mesh, causal=True,
                                           use_flash=True, blk_q=8,
                                           blk_k=8, interpret=True,
                                           window=12))

    def dense_loss(q, k, v):
      return jnp.sum(w * RA.full_attention(q, k, v, causal=True,
                                           window=12))

    got = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    ref = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, ref):
      np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                 atol=2e-4, rtol=2e-4)
