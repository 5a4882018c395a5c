"""Pallas kernel tests (interpret mode on CPU; the same kernels compile to
MXU/VPU code on TPU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.ops import flash_attention, layer_norm
from tensorflowonspark_tpu.parallel import ring_attention as ra


class TestLayerNorm:
  def _ref(self, x, w, eps=1e-6):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) *
            w.astype(jnp.float32)).astype(x.dtype)

  def test_forward_matches_reference(self):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 64, 128), jnp.float32)
    w = jnp.asarray(rng.rand(128) + 0.5, jnp.float32)
    out = layer_norm(x, w, blk_rows=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(self._ref(x, w)),
                               atol=1e-5, rtol=1e-5)

  def test_gradients_match_reference(self):
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 32, 64), jnp.float32)
    w = jnp.asarray(rng.rand(64) + 0.5, jnp.float32)
    t = jnp.asarray(rng.randn(2, 32, 64), jnp.float32)

    gk = jax.grad(lambda x, w: jnp.sum(
        t * layer_norm(x, w, blk_rows=16, interpret=True)),
        argnums=(0, 1))(x, w)
    gr = jax.grad(lambda x, w: jnp.sum(t * self._ref(x, w)),
                  argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gk[0]), np.asarray(gr[0]),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gk[1]), np.asarray(gr[1]),
                               atol=1e-4, rtol=1e-4)

  def test_indivisible_rows_handled(self):
    # 300 rows with blk_rows=128: block auto-shrinks to a divisor
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(3, 100, 64), jnp.float32)
    w = jnp.ones((64,), jnp.float32)
    out = layer_norm(x, w, blk_rows=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(self._ref(x, w)),
                               atol=1e-5, rtol=1e-5)

  def test_bfloat16(self):
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(8, 128), jnp.bfloat16)
    w = jnp.ones((128,), jnp.bfloat16)
    out = layer_norm(x, w, interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(self._ref(x, w), np.float32),
                               atol=3e-2, rtol=3e-2)

  def test_sharded_matches_dense(self):
    """Per-shard kernel over a data×sequence mesh == unsharded kernel."""
    from tensorflowonspark_tpu.ops import layer_norm_sharded
    from tensorflowonspark_tpu.parallel import mesh as M

    if len(jax.devices()) < 4:
      pytest.skip("needs 4 virtual devices")
    mesh = M.build_mesh(M.MeshSpec(data=2, sequence=2),
                        devices=jax.devices()[:4])
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(4, 32, 64), jnp.float32)
    w = jnp.asarray(rng.rand(64) + 0.5, jnp.float32)
    out = jax.jit(lambda x, w: layer_norm_sharded(
        x, w, mesh, interpret=True))(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(self._ref(x, w)),
                               atol=1e-5, rtol=1e-5)

  def test_sharded_gradients_match_dense(self):
    from tensorflowonspark_tpu.ops import layer_norm_sharded
    from tensorflowonspark_tpu.parallel import mesh as M

    if len(jax.devices()) < 4:
      pytest.skip("needs 4 virtual devices")
    mesh = M.build_mesh(M.MeshSpec(data=2, sequence=2),
                        devices=jax.devices()[:4])
    rng = np.random.RandomState(8)
    x = jnp.asarray(rng.randn(2, 16, 32), jnp.float32)
    w = jnp.asarray(rng.rand(32) + 0.5, jnp.float32)
    t = jnp.asarray(rng.randn(2, 16, 32), jnp.float32)

    gs = jax.jit(jax.grad(lambda x, w: jnp.sum(
        t * layer_norm_sharded(x, w, mesh, interpret=True)),
        argnums=(0, 1)))(x, w)
    gr = jax.grad(lambda x, w: jnp.sum(t * self._ref(x, w)),
                  argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gs[0]), np.asarray(gr[0]),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gs[1]), np.asarray(gr[1]),
                               atol=1e-4, rtol=1e-4)


class TestFlashAttention:
  @pytest.mark.parametrize("causal", [True, False])
  def test_matches_reference(self, causal):
    rng = np.random.RandomState(0)
    B, S, H, D = 2, 128, 4, 32
    q, k, v = (jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
               for _ in range(3))
    ref = ra.full_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, blk_q=32, blk_k=32,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

  def test_single_block(self):
    rng = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.randn(1, 16, 2, 8), jnp.float32)
               for _ in range(3))
    ref = ra.full_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

  def test_bfloat16_inputs(self):
    rng = np.random.RandomState(2)
    q, k, v = (jnp.asarray(rng.randn(1, 64, 2, 16), jnp.bfloat16)
               for _ in range(3))
    ref = ra.full_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, blk_q=32, blk_k=32,
                          interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)

  @pytest.mark.parametrize("mode", ["fused", "split"])
  def test_backward_modes_match_dense(self, mode):
    """Both backward plans — fused single-pass (default) and split
    two-kernel (``bwd="split"``, the fused plan's fallback) — produce dense-XLA
    gradients for q, k and v."""
    rng = np.random.RandomState(3)
    B, S, H, D = 2, 128, 4, 32
    q, k, v = (jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
               for _ in range(3))
    t = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    for causal in (True, False):
      ref = jax.grad(
          lambda q, k, v: jnp.sum(t * ra.full_attention(
              q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
      got = jax.grad(
          lambda q, k, v: jnp.sum(t * flash_attention(
              q, k, v, causal=causal, blk_q=32, blk_k=32,
              blk_bwd_q=32, blk_bwd_k=32,
              interpret=True, bwd=mode)), argnums=(0, 1, 2))(q, k, v)
      for a, b in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)

  def test_indivisible_seq_shrinks_blocks(self):
    # 100 doesn't divide by 32: blocks shrink to the largest divisor (25)
    # instead of asserting, and the result still matches dense attention
    key = jax.random.PRNGKey(5)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, 100, 2, 8), jnp.float32)
    k = jax.random.normal(kk, (1, 100, 2, 8), jnp.float32)
    v = jax.random.normal(kv, (1, 100, 2, 8), jnp.float32)
    out = flash_attention(q, k, v, blk_q=32, blk_k=32, interpret=True)
    ref = ra.full_attention(q, k, v, causal=True)
    assert jnp.max(jnp.abs(out - ref)) < 2e-5


class TestFlashAttentionGQA:
  """Grouped-query attention consumed natively by the flash kernels:
  grouped KV read straight through the remapped BlockSpec (no g× HBM
  expansion) and dK/dV accumulated across the query-head group inside
  the backward grid (round-3 verdict item 5 / ROADMAP deferral)."""

  def _data(self, B=2, S=128, H=8, HK=2, D=16, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, HK, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, HK, D), jnp.float32)
    t = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    return q, k, v, t

  @pytest.mark.parametrize("causal", [True, False])
  def test_forward_matches_expanded(self, causal):
    q, k, v, _ = self._data()
    H = q.shape[2]
    ref = ra.full_attention(q, ra.expand_heads(k, H), ra.expand_heads(v, H),
                            causal=causal)
    out = flash_attention(q, k, v, causal=causal, blk_q=32, blk_k=32,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

  @pytest.mark.parametrize("bwd", ["split", "fused"])
  def test_grads_match_expanded(self, bwd):
    """dK/dV arrive GROUPED (summed over each KV head's query group),
    matching AD through an explicit expand of the dense reference."""
    q, k, v, t = self._data(seed=1)
    H = q.shape[2]

    def loss_flash(q, k, v):
      return jnp.sum(t * flash_attention(q, k, v, causal=True, blk_q=32,
                                         blk_k=32, interpret=True, bwd=bwd))

    def loss_ref(q, k, v):
      return jnp.sum(t * ra.full_attention(
          q, ra.expand_heads(k, H), ra.expand_heads(v, H), causal=True))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    assert gf[1].shape == k.shape and gf[2].shape == v.shape
    for a, b in zip(gf, gr):
      np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                 atol=2e-5, rtol=2e-5)

  def test_mqa_single_kv_head(self):
    """MQA (one KV head for all queries) is the extreme group."""
    q, k, v, t = self._data(HK=1, seed=2)
    H = q.shape[2]
    ref = ra.full_attention(q, ra.expand_heads(k, H), ra.expand_heads(v, H),
                            causal=True)
    out = flash_attention(q, k, v, causal=True, blk_q=32, blk_k=32,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

  def test_indivisible_heads_raise(self):
    q, k, v, _ = self._data(H=8, HK=3)
    with pytest.raises(ValueError, match="divide"):
      flash_attention(q, k, v, interpret=True)

  def test_backward_plan_defaults_to_fused_and_refuses_others(self):
    """No ``bwd=`` means the fused plan (``_gqa_fused_fits`` alone sends it
    to split), and a plan that does not exist is refused by name."""
    from tensorflowonspark_tpu.ops.flash_attention import _resolve_bwd
    assert [_resolve_bwd(b) for b in (None, "fused", "split")] \
        == ["fused", "fused", "split"]
    q, k, v, _ = self._data()
    with pytest.raises(ValueError, match="bwd must be 'fused' or 'split'"):
      jax.grad(lambda q: jnp.sum(flash_attention(
          q, k, v, interpret=True, bwd="both")))(q)

  def test_fused_vmem_guard(self):
    """The grouped fused backward falls back to the split plan when its
    resident dK/dV + dQ blocks exceed the VMEM budget."""
    from tensorflowonspark_tpu.ops.flash_attention import _gqa_fused_fits
    assert _gqa_fused_fits(1024, 1024, 64, 2)       # bench GQA shape
    assert not _gqa_fused_fits(8192, 8192, 128, 2)  # long-context: split


class TestBlockPickers:
  """Mosaic accepts a second-minor block only when it is a multiple of 8
  (sublanes) or the whole dim. The picker must never snap to a bare
  divisor violating that."""

  def test_row_picker_sublane_aligned(self):
    from tensorflowonspark_tpu.ops.layer_norm import _pick_block
    assert _pick_block(16384, 128, 768) == 128
    assert _pick_block(96, 64, 768) == 48
    # no 8-aligned divisor (100 = 4*25): one full-dim block, never 50
    assert _pick_block(100, 64, 768) == 100
    # sub-floor request snaps UP to 8, not to the whole dimension
    assert _pick_block(16384, 4, 768) == 8


class TestSlidingWindow:
  """Sliding-window attention (window = last W positions, self included):
  the kernels must equal the dense windowed mask exactly while bounding
  their block loops to the window (the O(seq·window) claim)."""

  def _qkv(self, B=2, S=128, H=4, D=16, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
                 for _ in range(3))

  @pytest.mark.parametrize("window", [1, 16, 40, 128, 500])
  def test_forward_matches_dense_window(self, window):
    q, k, v = self._qkv()
    out = flash_attention(q, k, v, causal=True, blk_q=32, blk_k=32,
                          interpret=True, window=window)
    ref = ra.full_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

  @pytest.mark.parametrize("bwd", ["fused", "split"])
  def test_grads_match_dense_window(self, bwd):
    q, k, v = self._qkv()
    t = jnp.asarray(np.random.RandomState(9).randn(*q.shape), jnp.float32)
    ref = jax.grad(
        lambda q, k, v: jnp.sum(t * ra.full_attention(
            q, k, v, causal=True, window=40)), argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(
        lambda q, k, v: jnp.sum(t * flash_attention(
            q, k, v, causal=True, blk_q=32, blk_k=32, blk_bwd_q=32,
            blk_bwd_k=32, interpret=True, bwd=bwd,
            window=40)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, ref):
      np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                 atol=2e-4, rtol=2e-4)

  @pytest.mark.parametrize("bwd", ["fused", "split"])
  def test_gqa_windowed_grads(self, bwd):
    q, k, v = self._qkv()
    kg, vg = k[:, :, :2, :], v[:, :, :2, :]
    t = jnp.asarray(np.random.RandomState(9).randn(*q.shape), jnp.float32)
    ref = jax.grad(
        lambda q, kk, vv: jnp.sum(t * ra.full_attention(
            q, ra.expand_heads(kk, 4), ra.expand_heads(vv, 4),
            causal=True, window=24)), argnums=(0, 1, 2))(q, kg, vg)
    got = jax.grad(
        lambda q, kk, vv: jnp.sum(t * flash_attention(
            q, kk, vv, causal=True, blk_q=32, blk_k=32, blk_bwd_q=32,
            blk_bwd_k=32, interpret=True, bwd=bwd,
            window=24)), argnums=(0, 1, 2))(q, kg, vg)
    for a, b in zip(got, ref):
      np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                 atol=2e-4, rtol=2e-4)

  def test_ring_block_partials_merge_across_window(self):
    """Two sequence shards with the window straddling the boundary: the
    merged block partials must equal the dense windowed reference (the
    ring-attention composition path)."""
    from tensorflowonspark_tpu.ops import (flash_attention_block,
                                           merge_partials)
    q, k, v = self._qkv()
    half = 64
    o1, l1 = flash_attention_block(q[:, half:], k[:, :half], v[:, :half],
                                   half, 0, causal=True, blk_q=32,
                                   blk_k=32, interpret=True, window=40)
    o2, l2 = flash_attention_block(q[:, half:], k[:, half:], v[:, half:],
                                   half, half, causal=True, blk_q=32,
                                   blk_k=32, interpret=True, window=40)
    merged, _ = merge_partials(o1, l1, o2, l2)
    ref = ra.full_attention(q, k, v, causal=True, window=40)[:, half:]
    np.testing.assert_allclose(np.asarray(merged), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

  def test_out_of_window_block_is_fully_masked(self):
    """A remote KV block entirely behind the window contributes nothing:
    lse = NEG_INF everywhere, so merge_partials ignores it."""
    from tensorflowonspark_tpu.ops import flash_attention_block
    from tensorflowonspark_tpu.ops.flash_attention import NEG_INF
    q, k, v = self._qkv(S=64)
    # queries at absolute positions [1024, 1088); KV at [0, 64): with
    # window 128 every pair is out of range
    _, lse = flash_attention_block(q, k, v, 1024, 0, causal=True,
                                   blk_q=32, blk_k=32, interpret=True,
                                   window=128)
    assert np.all(np.asarray(lse) <= NEG_INF)

  def test_window_requires_causal(self):
    q, k, v = self._qkv(S=32)
    with pytest.raises(ValueError, match="causal"):
      flash_attention(q, k, v, causal=False, interpret=True, window=8)

  def test_loop_bounds_scale_with_window(self):
    """The windowed kernel must do O(window), not O(seq), work: check the
    block-loop bounds directly (lo..hi spans ≤ window/blk_k + 2 blocks)."""
    from tensorflowonspark_tpu.ops.flash_attention import (_causal_k_hi,
                                                           _window_k_lo)
    blk_q = blk_k = 32
    n_kblocks = 64   # seq 2048
    window = 128
    for qi in range(64):
      hi = int(_causal_k_hi(qi, 0, 0, blk_q, blk_k, n_kblocks))
      lo = int(_window_k_lo(qi, 0, 0, blk_q, blk_k, window, n_kblocks))
      visited = hi - lo
      assert visited <= window // blk_k + 2
      # every visited block must contain at least one unmasked pair
      assert lo * blk_k <= qi * blk_q                      # not past diag
      assert (hi * blk_k) > qi * blk_q - window            # window reaches
