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


# --- the kernels multiply at their operands' width (PR 46) -------------------
#
# One case a call path. ``_width_case(name, dtype)`` gives ``(fn, args, ref)``:
# ``fn(*args)`` runs the kernels in interpret mode and returns a tuple of
# arrays; ``ref(lo)`` is the same result from a dense float32 attention that
# rounds the probabilities (and dS) to ``lo`` before their products, as the
# kernels do to a float32 intermediate whose other operand is ``lo`` wide.

_WIDTH_CASES = ("forward", "fused_backward", "split_backward", "gqa_fused",
                "gqa_split", "keep_operand", "window", "keys_192_values_128",
                "block_merge")


def _dense_rounded(q, k, v, t, lo, causal=True, window=None, keep=None,
                   scale=None, q_base=0):
  """``(out, dq, dk, dv)`` of ``sum(t * attention(q, k, v))`` in float32 with
  the flash kernels' formulas: out = round(e)·v / Σe; P = e / Σe, dP = dO·vᵀ,
  Δ = Σ dO ⊙ out, dS = P ⊙ (dP − Δ); dV = round(P)ᵀ·dO, dK = scale ·
  round(dS)ᵀ·q, dQ = scale · round(dS)·k. Grouped K/V are expanded and their
  gradients summed over each group."""
  h, hk = q.shape[2], k.shape[2]
  f32 = lambda x: x.astype(jnp.float32)      # noqa: E731
  rnd = lambda x: f32(x.astype(lo))          # noqa: E731
  ke, ve = f32(ra.expand_heads(k, h)), f32(ra.expand_heads(v, h))
  if scale is None:
    scale = q.shape[-1] ** -0.5
  s = jnp.einsum("bqhd,bkhd->bhqk", f32(q), ke) * scale
  q_pos = q_base + jnp.arange(q.shape[1])[:, None]
  k_pos = jnp.arange(k.shape[1])[None, :]
  mask = jnp.ones(s.shape[-2:], bool)
  if causal:
    mask = k_pos <= q_pos
    if window is not None:
      mask = jnp.logical_and(mask, k_pos > q_pos - window)
  mask = mask[None, None]
  if keep is not None:
    mask = jnp.logical_and(mask, (keep != 0)[:, None])
  s = jnp.where(mask, s, -1e30)
  e = jnp.where(mask, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
  total = e.sum(-1, keepdims=True)
  out = (jnp.einsum("bhqk,bkhd->bqhd", rnd(e), ve)
         / total[:, :, :, 0].transpose(0, 2, 1)[..., None]).astype(q.dtype)
  if t is None:
    return (out,)
  p = e / total
  do = f32(t)
  delta = jnp.sum(do * f32(out), -1).transpose(0, 2, 1)[..., None]
  ds = p * (jnp.einsum("bqhd,bkhd->bhqk", do, ve) - delta)
  dv = jnp.einsum("bhqk,bqhd->bkhd", rnd(p), do)
  dk = jnp.einsum("bhqk,bqhd->bkhd", rnd(ds), f32(q)) * scale
  dq = jnp.einsum("bhqk,bkhd->bqhd", rnd(ds), ke) * scale
  b, sk = k.shape[:2]
  dk, dv = (x.reshape(b, sk, hk, h // hk, -1).sum(3) for x in (dk, dv))
  return out, dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _width_case(name, dtype):
  from tensorflowonspark_tpu.ops import flash_attention_block, merge_partials
  rng = np.random.RandomState(_WIDTH_CASES.index(name))
  B, S, H, D = 1, 64, 4, 16          # head_dim 16: the scale is a power of two
  hk = 2 if name.startswith("gqa") or name == "keys_192_values_128" else H
  d, dv = (192, 128) if name == "keys_192_values_128" else (D, D)
  q = jnp.asarray(rng.randn(B, S, H, d), dtype)
  k = jnp.asarray(rng.randn(B, S, hk, d), dtype)
  v = jnp.asarray(rng.randn(B, S, hk, dv), dtype)
  t = jnp.asarray(rng.randn(B, S, H, dv), dtype)
  blocks = dict(blk_q=32, blk_k=32, blk_bwd_q=32, blk_bwd_k=32,
                interpret=True)
  # the call, what the dense reference is told of it, and whether the case
  # takes gradients (a keep operand and heads of two widths are forward only)
  call, told, grads = flash_attention, {}, True
  if name == "forward":
    grads = False
  elif name in ("fused_backward", "split_backward", "gqa_fused", "gqa_split"):
    blocks["bwd"] = "fused" if "fused" in name else "split"
  elif name == "keep_operand":
    told["keep"] = jnp.asarray(np.logical_or(
        rng.rand(B, S, S) < 0.5, np.eye(S, dtype=bool)[None]), jnp.int8)
    grads = False
  elif name == "window":
    told["window"] = 24
  elif name == "keys_192_values_128":
    # 192 ** -0.5 is no power of two; a latent layer hands the kernel its own
    # scale, and so does this case
    told["scale"] = 0.0625
    call, grads = (lambda q, k, v, **kw: flash_attention_block(
        q, k, v, 0, 0, **kw)[0]), False
  else:
    assert name == "block_merge"
    half = S // 2

    def call(q, k, v, **kw):
      parts = [flash_attention_block(q, k[:, lo:lo + half], v[:, lo:lo + half],
                                     0, lo, **kw) for lo in (0, half)]
      return merge_partials(*parts[0], *parts[1])[0]

  def fn(q, k, v):
    if not grads:
      return (call(q, k, v, **told, **blocks),)
    out, vjp = jax.vjp(lambda *a: call(*a, **told, **blocks), q, k, v)
    return (out,) + vjp(t)

  return fn, (q, k, v), lambda lo: _dense_rounded(
      q, k, v, t if grads else None, lo, **told)


def _kernel_dots(fn, args):
  """Every ``dot_general`` inside a Pallas call of ``fn``'s jaxpr, as its two
  operands' dtypes."""
  found = []

  def walk(jaxpr, inside):
    for eqn in jaxpr.eqns:
      if inside and eqn.primitive.name == "dot_general":
        found.append(tuple(x.aval.dtype for x in eqn.invars))
      here = inside or eqn.primitive.name == "pallas_call"
      for sub in jax.core.jaxprs_in_params(eqn.params):
        walk(sub, here)
  walk(jax.make_jaxpr(fn)(*args).jaxpr, False)
  return found


# float32 callers: bits of three entries of every output (flat indices 0,
# size // 2, -1) and the float64 sum of all of them, as the tree before PR 46
# gave them in interpret mode
_WIDTH_PINS = {
    "forward": [
        [3204933429, 3204558732, 3160235439, "-0x1.58a8c9c42f980p+6"],
    ],
    "fused_backward": [
        [3206967567, 1035695896, 3178991340, "0x1.54a4a4f1a2600p+6"],
        [0, 1050001634, 3203485861, "-0x1.379b136141aa0p+1"],
        [3164599072, 3163528063, 978851348, "0x1.4e52c80000000p-19"],
        [1053600794, 1035683874, 3130384547, "0x1.3b2fdc8977c00p+4"],
    ],
    "split_backward": [
        [1049189874, 3173625635, 3193237168, "0x1.445589c86d400p+6"],
        [863965384, 1056596368, 3191434026, "-0x1.1ce52d00f4752p+4"],
        [1045733472, 1039512236, 973113556, "-0x1.63b5980000000p-19"],
        [3218444989, 3183933439, 1014629103, "0x1.a1477cad16280p+6"],
    ],
    "gqa_fused": [
        [3197324623, 3182164194, 1047346715, "-0x1.2d070b7181b38p+7"],
        [863235108, 3192774219, 3178784112, "0x1.416e19aa4f6bap+4"],
        [1061355868, 1042394318, 3160956620, "-0x1.61c4000000000p-20"],
        [1066640432, 3166633864, 3163459313, "-0x1.4dc76ea798000p+4"],
    ],
    "gqa_split": [
        [1060658957, 3194364756, 1048785243, "-0x1.2c1fda0973000p+0"],
        [0, 3173867046, 3192014024, "0x1.c7b8b96ba0000p+3"],
        [3199318954, 3183321830, 3140257348, "0x1.db1a000000000p-19"],
        [3229183754, 1054926062, 3133189216, "-0x1.25c440d5e8000p+5"],
    ],
    "keep_operand": [
        [1053495039, 3200813825, 3193748231, "-0x1.18fb7f4280c00p+5"],
    ],
    "window": [
        [3190642396, 3172296145, 3205673114, "-0x1.ed99b55670000p+5"],
        [0, 3197515316, 1048130542, "0x1.235e82a40add0p+4"],
        [1058148867, 1032901822, 3173915727, "0x1.a034000000000p-22"],
        [1070596335, 1047750874, 1034070716, "-0x1.5773041186700p+6"],
    ],
    "keys_192_values_128": [
        [3208043063, 3191495982, 3176944183, "-0x1.a3ca12aa64e60p+5"],
    ],
    "block_merge": [
        [3216741609, 3199479229, 1043899728, "0x1.cab666fa45800p+2"],
        [0, 3181727969, 1047462980, "-0x1.42bad611c0713p+4"],
        [1051186834, 3200063286, 3176819537, "-0x1.4c37e00000000p-20"],
        [3213217936, 3176148752, 984121120, "-0x1.4e9985fdcb400p+5"],
    ],
}


def _pins(outs):
  pins = []
  for x in outs:
    flat = np.asarray(x, np.float32).ravel()
    pins.append([int(b) for b in flat[[0, flat.size // 2, -1]].view(np.uint32)]
                + [float(flat.astype(np.float64).sum()).hex()])
  return pins


@pytest.mark.parametrize("name", _WIDTH_CASES)
def test_flash_kernels_multiply_at_their_operands_width(name):
  """bf16 operands go to the MXU as bf16 (no product inside a kernel has a
  float32 operand) and the result is a dense attention's that rounds P and dS
  to bf16; float32 callers keep float32 products and the results they had."""
  fn, args, ref = _width_case(name, jnp.bfloat16)
  dots = _kernel_dots(fn, args)
  assert dots and all(dt == jnp.bfloat16 for pair in dots for dt in pair), dots
  # against the float32 dense attention these gaps need 6e-3; the merge
  # rounds each partial's output to bf16 once more
  tol = 7e-3 if name == "block_merge" else 4e-3
  for got, want in zip(fn(*args), ref(jnp.bfloat16)):
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)

  fn, args, ref = _width_case(name, jnp.float32)
  dots = _kernel_dots(fn, args)
  assert dots and all(dt == jnp.float32 for pair in dots for dt in pair), dots
  outs = fn(*args)
  for got, want in zip(outs, ref(jnp.float32)):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
  assert _pins(outs) == _WIDTH_PINS[name]
