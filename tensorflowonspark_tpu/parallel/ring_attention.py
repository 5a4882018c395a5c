"""Ring attention: sequence/context parallelism for long sequences.

A first-class capability of this framework that the reference lacked
entirely (SURVEY.md §5 "Long-context / sequence parallelism: absent") — on
TPU it is what makes the ``sequence`` mesh axis real: Q stays resident per
shard while K/V blocks rotate around the ICI ring (``lax.ppermute``), with a
numerically-stable online-softmax accumulation so the result is exactly
full attention over the global sequence.

Compute cost per device: n_steps × block attention; communication overlaps
with compute because each step's ppermute of the *next* KV block is
independent of the current block's math (XLA schedules the overlap).

Layout: [batch, seq, heads, head_dim] with seq sharded over the
``sequence`` axis; inside the shard_map body every ref sees its local
sequence block.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from tensorflowonspark_tpu.parallel import mesh as mesh_lib
from tensorflowonspark_tpu.utils import compat

NEG_INF = -1e30


def expand_heads(kv, num_heads: int):
  """Broadcast grouped-query KV heads up to the query head count (KV head
  j serves query heads [j*g, (j+1)*g) — blocked layout). Under GQA the
  ring permutes the UNEXPANDED blocks — a num_heads/kv_heads cut in ICI
  traffic. The flash path consumes them unexpanded too (the kernels'
  grouped-aware KV BlockSpec + cross-head dK/dV grid accumulation,
  ops.flash_attention module docstring — the round-3 ROADMAP deferral,
  closed); only the dense block math expands, and its einsum fuses the
  repeat. The ONE head-broadcast helper — models/transformer.py uses it
  too, so the grouping convention cannot drift."""
  hk = kv.shape[2]
  if hk == num_heads:
    return kv
  if num_heads % hk:
    raise ValueError("kv heads (%d) must divide query heads (%d)"
                     % (hk, num_heads))
  return jnp.repeat(kv, num_heads // hk, axis=2)


_expand_heads = expand_heads


def _block_attn(q, k, v, m, l, o, q_offset, kv_offset, causal, scale,
                window=None):
  """One online-softmax accumulation step against a single KV block.

  q: [B, Sq, H, D]; k/v: [B, Sk, H, D]; m/l: [B, H, Sq]; o: [B, Sq, H, D].
  Positions are global offsets so causal masking works across shards.
  ``window``: sliding-window mask (last ``window`` positions, self
  included) — same convention as ops.flash_attention.
  """
  qf = q.astype(jnp.float32)
  kf = k.astype(jnp.float32)
  scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * scale  # [B,H,Sq,Sk]

  if causal:
    q_pos = q_offset + lax.broadcasted_iota(jnp.int32, (q.shape[1], k.shape[1]), 0)
    k_pos = kv_offset + lax.broadcasted_iota(jnp.int32, (q.shape[1], k.shape[1]), 1)
    keep = k_pos <= q_pos
    if window is not None:
      keep = jnp.logical_and(keep, k_pos > q_pos - window)
    mask = keep[None, None]
    scores = jnp.where(mask, scores, NEG_INF)

  m_block = jnp.max(scores, axis=-1)                      # [B,H,Sq]
  m_new = jnp.maximum(m, m_block)
  # guard fully-masked rows (m_new == NEG_INF) against NaNs
  m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
  p = jnp.exp(scores - m_safe[..., None])
  p = jnp.where(scores <= NEG_INF, 0.0, p)
  correction = jnp.where(m <= NEG_INF, 0.0, jnp.exp(m - m_safe))
  l_new = l * correction + jnp.sum(p, axis=-1)
  pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
  o_new = o * correction.transpose(0, 2, 1)[..., None] + pv
  return m_new, l_new, o_new


def _ring_attn_local(q, k, v, axis_name: str, causal: bool, window=None):
  """shard_map body: full attention with KV blocks rotating around the ring."""
  n = compat.jax_axis_size(axis_name)
  my = lax.axis_index(axis_name)
  b, s_local, h, d = q.shape
  scale = 1.0 / (d ** 0.5)

  m0 = jnp.full((b, h, s_local), NEG_INF, jnp.float32)
  l0 = jnp.zeros((b, h, s_local), jnp.float32)
  o0 = jnp.zeros((b, s_local, h, d), jnp.float32)
  q_offset = my * s_local

  def body(step, carry):
    k_blk, v_blk, m, l, o = carry
    src = (my - step) % n                 # whose block we hold this step
    kv_offset = src * s_local
    m, l, o = _block_attn(q, _expand_heads(k_blk, h),
                          _expand_heads(v_blk, h), m, l, o, q_offset,
                          kv_offset, causal, scale, window)
    # rotate kv to the next neighbor (ICI ring); last rotation is unused but
    # keeps the loop shape static for XLA
    perm = [(i, (i + 1) % n) for i in range(n)]
    k_blk = lax.ppermute(k_blk, axis_name, perm)
    v_blk = lax.ppermute(v_blk, axis_name, perm)
    return k_blk, v_blk, m, l, o

  _, _, m, l, o = lax.fori_loop(0, n, body, (k, v, m0, l0, o0))
  l = jnp.where(l == 0.0, 1.0, l)         # fully-masked rows -> zeros
  out = o / l.transpose(0, 2, 1)[..., None]
  return out.astype(q.dtype)


def _ring_flash_local(q, k, v, axis_name: str, causal: bool, blk_q: int,
                      blk_k: int, interpret: bool, blk_bwd_q=None,
                      blk_bwd_k=None, bwd=None, window=None):
  """shard_map body: ring attention with Pallas flash-attention blocks.

  Each ring step computes the partial attention of the local queries
  against the currently-held KV block with the fused kernel
  (ops.flash_attention_block) and merges the normalized partials via
  their logsumexps — the fused-kernel memory profile composed with
  sequence parallelism.
  """
  from tensorflowonspark_tpu.ops.flash_attention import (
      NEG_INF as _NEG_INF, flash_attention_block, merge_partials)

  n = compat.jax_axis_size(axis_name)
  my = lax.axis_index(axis_name)
  b, s_local, h, d = q.shape

  # accumulate the running output in float32 across ring steps (a bf16
  # carry would round n times); cast to the input dtype once at the end
  o0 = jnp.zeros(q.shape, jnp.float32)
  lse0 = jnp.full((b, h, s_local), _NEG_INF, jnp.float32)

  def body(step, carry):
    k_blk, v_blk, o, lse = carry
    src = (my - step) % n
    # grouped KV feeds the kernel UNEXPANDED: the flash kernels carry a
    # grouped-aware KV BlockSpec (query head -> its KV head row) with
    # cross-head dK/dV accumulation in the backward grid, so the expanded
    # block never exists — not in HBM, not per step
    o_j, lse_j = flash_attention_block(
        q, k_blk, v_blk,
        my * s_local, src * s_local, causal=causal,
        blk_q=blk_q, blk_k=blk_k, interpret=interpret,
        blk_bwd_q=blk_bwd_q, blk_bwd_k=blk_bwd_k, bwd=bwd,
        window=window)
    o, lse = merge_partials(o, lse, o_j.astype(jnp.float32), lse_j)
    perm = [(i, (i + 1) % n) for i in range(n)]
    k_blk = lax.ppermute(k_blk, axis_name, perm)
    v_blk = lax.ppermute(v_blk, axis_name, perm)
    return k_blk, v_blk, o, lse

  _, _, o, _ = lax.fori_loop(0, n, body, (k, v, o0, lse0))
  return o.astype(q.dtype)


def ring_attention(q, k, v, mesh, causal: bool = True,
                   axis_name: str = mesh_lib.AXIS_SEQUENCE,
                   batch_axes=None, use_flash: bool = False,
                   blk_q: int = 256, blk_k: int = 512,
                   interpret: bool = False, blk_bwd_q: int = None,
                   blk_bwd_k: int = None, bwd: str = None,
                   window: int = None):
  """Exact full attention over a sequence sharded across ``axis_name``.

  Args:
    q, k, v: [batch, seq, heads, head_dim], seq sharded over ``axis_name``.
      K/V may carry FEWER heads than Q (grouped-query attention): the ring
      then permutes the small grouped blocks — ICI traffic drops by
      num_heads/kv_heads — and every step expands them locally before the
      block math. (If a tensor axis shards heads and cannot divide the
      grouped count, K/V are expanded up front instead.)
    mesh: the device mesh.
    causal: apply a global causal mask.
    batch_axes: mesh axes dim 0 is sharded over (defaults to data+fsdp).
    use_flash: compute each ring step's block with the fused Pallas kernel
      (ops.flash_attention_block) instead of dense block math — the
      memory-optimal path on TPU (``interpret=True`` for CPU tests).
      ``blk_q``/``blk_k`` tile the forward; ``blk_bwd_q``/``blk_bwd_k``
      tile the backward (None = per-mode DEFAULT_BWD_BLOCKS); ``bwd``
      picks the backward implementation per call ("fused"/"split",
      None = "fused") — the same per-call
      override flash_attention itself offers.

  Returns attention output with the same sharding as ``q``.
  """
  from tensorflowonspark_tpu.utils.compat import jax_shard_map as shard_map

  batch_axes = batch_axes if batch_axes is not None else \
      mesh_lib.data_axes(mesh)
  t = mesh_lib.axis_size(mesh, mesh_lib.AXIS_TENSOR)
  if k.shape[2] != q.shape[2] and k.shape[2] % max(1, t) != 0:
    # heads are tensor-sharded and the grouped count can't divide: expand
    # up front (the pre-GQA behavior) rather than break the head spec
    k = _expand_heads(k, q.shape[2])
    v = _expand_heads(v, q.shape[2])
  spec = P(batch_axes or None, axis_name, mesh_lib.AXIS_TENSOR
           if mesh_lib.AXIS_TENSOR in mesh.axis_names else None, None)
  if window is not None and not causal:
    raise ValueError("sliding-window ring attention requires causal=True")
  if use_flash:
    fn = functools.partial(_ring_flash_local, axis_name=axis_name,
                           causal=causal, blk_q=blk_q, blk_k=blk_k,
                           blk_bwd_q=blk_bwd_q, blk_bwd_k=blk_bwd_k, bwd=bwd,
                           interpret=interpret, window=window)
  else:
    fn = functools.partial(_ring_attn_local, axis_name=axis_name,
                           causal=causal, window=window)
  return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=False)(q, k, v)


def full_attention(q, k, v, causal: bool = True, window: int = None):
  """Single-device reference implementation (for tests and small models).
  ``window`` masks like the flash kernels' sliding window (each query sees
  its last ``window`` positions, self included) but materializes the
  dense mask — O(s²) memory, reference only."""
  if window is not None and not causal:
    raise ValueError("sliding-window attention requires causal=True "
                     "(same contract as ops.flash_attention)")
  b, s, h, d = q.shape
  scale = 1.0 / (d ** 0.5)
  scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                      k.astype(jnp.float32)) * scale
  if causal:
    mask = jnp.tril(jnp.ones((s, s), bool))
    if window is not None:
      mask = jnp.logical_and(mask, ~jnp.tril(jnp.ones((s, s), bool),
                                             k=-window))
    scores = jnp.where(mask[None, None], scores, NEG_INF)
  probs = jax.nn.softmax(scores, axis=-1)
  out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
  return out.astype(q.dtype)
