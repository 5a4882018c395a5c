"""Fused attention as Pallas TPU kernels — forward, backward, and
ring-composable block partials.

Flash-attention-style: the forward streams over K/V blocks with an online
softmax carried in VMEM scratch, so the [Sq, Sk] score matrix never hits
HBM — scores are produced on the MXU, normalized on the VPU, accumulated
in float32 while inputs stay bfloat16.

Every MXU product takes its operands AT THE WIDTH THE CALLER'S ARRAYS HAVE,
with a float32 accumulator (``_dot``). The two products of two array blocks,
``S = Q·Kᵀ`` and ``dP = dO·Vᵀ``, go in as they are loaded (bf16 x bf16 is
exact in float32; only the order of the sum differs from a float32 product),
and the softmax scale is applied to the float32 SCORES, not to ``q`` (exact at
every head width; ``q * scale`` in bf16 is only where the scale is a power of
two). The products with a float32 INTERMEDIATE on one side (``P·V``; ``Pᵀ·dO``,
``dSᵀ·Q``, ``dS·K``) round that intermediate to the array operand's dtype just
before the product, as a bf16 model's stated precision does; since ``q`` is
not pre-scaled, ``dK = scale · Σ dSᵀ·Q`` and ``dQ = scale · Σ dS·K`` take the
scale on their float32 sums. The online-softmax state (``m``, ``l``, ``acc``),
the logsumexp, Δ and the dQ/dK/dV accumulators stay float32. The operands'
dtype alone decides: float32 callers keep float32 products (and, where the
scale is a power of two, the bits they had). What this buys on the chip is
the scale's exactness and no upcast a load, NOT passes of the MXU: a float32
product inside a Mosaic kernel at the default precision already rounds its
operands to bf16 and makes one pass (v5e, PR 46: the kernels' results and
times were the same to the last digit either way). The kernels are bound by
the LATENCY of a block pair's dependent chain (scores, max, exp, P·V: about
0.4 us a pair whatever its size), so fewer and larger pairs win
(``_default_bwd_blocks``).

The positional mask is one compare a pair: a row-less-column iota, formed
once a kernel (``_rel``), against the pair's scalar offset. A masked entry's
probability needs no second ``where``: ``exp(NEG_INF - m)`` is 0 by itself,
and a fully masked row (``m`` or the logsumexp read as 0) holds only masked
entries.

The forward also emits the per-row logsumexp; the backward (standard Δ
correction, dense scores never materialized) defaults to ONE single-pass
kernel producing dQ/dK/dV per k-block with dQ accumulated in a
grid-resident VMEM block — 5 MXU matmuls per (q, k) block pair;
``bwd="split"`` selects the two-kernel plan (dQ over q-blocks, dK/dV over
k-blocks; 7 matmuls/pair), and the fused plan
falls back to it where its resident accumulators do not fit VMEM
(``_gqa_fused_fits``). Backward block sizes resolve separately from the
forward's (``DEFAULT_BWD_BLOCKS``).

:func:`flash_attention` is full (self-)attention. :func:`flash_attention_block`
computes a PARTIAL attention of local queries against one remote KV block
(absolute position bases passed as traced scalars) and returns
(normalized-partial output, logsumexp) — the building block
``parallel.ring_attention`` merges across ring steps; its custom VJP
accepts cotangents for both outputs (the lse cotangent folds into Δ).

``interpret=True`` runs the same kernels on CPU for tests. Layout:
[batch, seq, heads, head_dim].

The FORWARD takes values of another width than the keys (``v [batch, seq,
kv_heads, dv]``: scores contract over ``head_dim``, the output is ``dv``
wide); the backward kernels take one ``head_dim`` and refuse such heads by
name.

Grouped-query attention is native: K/V may carry ``heads / g`` heads (KV
head j serves query heads [j·g, (j+1)·g) — the blocked convention shared
with ``parallel.ring_attention.expand_heads``). The forward and dQ
kernels just remap their KV BlockSpec row (query head → its KV head), so
the grouped block is read straight from HBM with no g× expansion; dK/dV
accumulate across the g query heads inside the grid (see the ``_gqa``
kernels) instead of summing an expanded cotangent.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Per-row statistics (logsumexp, Δ) ride through kernels with this many
# trailing lanes: Mosaic's layout verifier rejects blocked 1-D operands and
# (1, blk) blocks of 2-D arrays, but a [rows, LANES] array blocked
# (blk, LANES) satisfies the (8, 128)-or-full-dim tiling rule with 16×
# less padding than a full 128-lane broadcast.
LANES = 8


# dot_general contractions of two 2-D blocks: a·b, a·bᵀ, aᵀ·b
_NN = ((1,), (0,))
_NT = ((1,), (1,))
_TN = ((0,), (0,))


def _dot(a, b, contract=_NN):
  """One MXU product at ITS OPERANDS' width with a float32 accumulator: bf16
  blocks go in as bf16 (a bf16 x bf16 product is exact in float32), float32
  blocks as float32 (on the TPU one bf16 pass too, at the default precision:
  module docstring). Two array blocks of different widths meet at the wider;
  a float32 intermediate (P, dS) is rounded by its CALLER to the array
  operand's dtype first."""
  dtype = jnp.promote_types(a.dtype, b.dtype)
  return lax.dot_general(a.astype(dtype), b.astype(dtype), (contract, ((), ())),
                         preferred_element_type=jnp.float32)


def _rel(blk_q, blk_k):
  """Row index less column index over one (q-block, k-block) pair: the part
  of the positional mask that no pair changes, formed ONCE a kernel, outside
  its block loop."""
  return (lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
          - lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1))


def _masked_scores(q, k, scale, rel, qi, ki, blk_q, blk_k, causal, q_base=0,
                   k_base=0, window=None, keep=None):
  """Scaled scores ``(q·kᵀ) * scale`` (the scale on the float32 scores) for
  one (q-block, k-block) pair with causal masking. ``rel`` is :func:`_rel`
  of the pair's shape (None without a causal mask): ``k_pos <= q_pos`` is
  ``rel >= first k position - first q position``, one compare against a
  scalar a pair.

  ``q_base``/``k_base`` are absolute position offsets (traced scalars are
  fine) so the same kernel works for ring-attention blocks where the KV
  block comes from another sequence shard. ``window`` (sliding-window
  attention, Mistral convention: each query attends to the ``window``
  most recent positions including itself) additionally masks
  ``k_pos <= q_pos - window``; the loop-bound helpers below skip blocks
  the mask would zero entirely, so FLOPs scale with the window, not the
  sequence. ``keep`` (``[blk_q, blk_k]``, nonzero = attend; the forward's
  optional operand, a mask BY QUERY shared by the heads) is AND-ed with the
  positional mask.
  """
  s = _dot(q, k, _NT) * scale
  if keep is not None:
    s = jnp.where(keep.astype(jnp.int32) != 0, s, NEG_INF)
  if causal:
    ahead = (k_base + ki * blk_k) - (q_base + qi * blk_q)
    keep = rel >= ahead
    if window is not None:
      keep = jnp.logical_and(keep, rel < ahead + window)
    s = jnp.where(keep, s, NEG_INF)
  return s


def _causal_k_hi(qi, q_base, k_base, blk_q, blk_k, n_kblocks):
  """Exclusive upper bound on k-blocks visible to q-block ``qi`` under the
  causal mask — blocks past the diagonal are fully masked, so the online-
  softmax loop skips them instead of exp()-ing NEG_INF (≈2× FLOPs saved
  at equal bases; rides the ring offsets for sequence parallelism)."""
  q_hi = q_base + (qi + 1) * blk_q - 1      # max absolute q position
  return jnp.clip((q_hi - k_base) // blk_k + 1, 0, n_kblocks)


def _window_k_lo(qi, q_base, k_base, blk_q, blk_k, window, n_kblocks):
  """First k-block with any position inside q-block ``qi``'s window —
  the lower loop bound that makes sliding-window FLOPs O(window)."""
  k_lo = q_base + qi * blk_q - (window - 1) - k_base   # min visible k pos
  return jnp.clip(k_lo // blk_k, 0, n_kblocks)


def _causal_q_lo(ki, q_base, k_base, blk_q, blk_k):
  """First q-block with any row at-or-past k-block ``ki``'s start."""
  k_lo = k_base + ki * blk_k - q_base       # min k position, q-relative
  return jnp.clip(k_lo // blk_q, 0, None)


def _window_q_hi(ki, q_base, k_base, blk_q, blk_k, window, n_qblocks):
  """Exclusive upper bound on q-blocks that can still see k-block ``ki``
  under a sliding window (rows further ahead have slid past it)."""
  q_hi = k_base + (ki + 1) * blk_k - 1 + (window - 1) - q_base
  return jnp.clip(q_hi // blk_q + 1, 0, n_qblocks)


def _pair_p_ds(s, lse, delta, do, v):
  """Shared backward math for one (q, k) block pair — P recomputed from
  the forward's logsumexp (a masked entry is ``exp(NEG_INF - lse) == 0`` by
  itself, and in a fully-masked row, where ``lse`` reads as 0, every entry is
  masked), then dP = dO·Vᵀ and dS = P ⊙ (dP − Δ), both float32. Used by all the backward
  kernels (dQ, dK/dV, fused, grouped) so a masking/Δ fix lands everywhere at
  once."""
  lse_safe = jnp.where(lse <= NEG_INF, 0.0, lse)
  p = jnp.exp(s - lse_safe)
  ds = p * (_dot(do, v, _NT) - delta)
  return p, ds


# --- kernels ---------------------------------------------------------------


def _attn_fwd_kernel(qb_ref, kb_ref, q_ref, k_ref, v_ref, *rest,
                     blk_q: int, blk_k: int, kv_len: int, causal: bool,
                     scale: float, window=None):
  # an optional KEEP operand ([1, blk_q, kv_len] int8, this q-block's rows of
  # a mask by query) sits between the inputs and the two outputs
  keep_ref = rest[0] if len(rest) == 3 else None
  o_ref, lse_ref = rest[-2:]
  qi = pl.program_id(1)
  q_base = qb_ref[0]
  k_base = kb_ref[0]
  q = q_ref[0]                                      # [blk_q, D]
  n_kblocks = kv_len // blk_k
  rel = _rel(blk_q, blk_k) if causal else None

  def body(ki, carry):
    m, l, acc = carry                               # [blk_q,1] ×2, [blk_q,D]
    # block loads straight from VMEM refs — dynamic_slice on a loaded
    # value has no Mosaic lowering
    k = k_ref[0, pl.ds(ki * blk_k, blk_k), :]
    v = v_ref[0, pl.ds(ki * blk_k, blk_k), :]
    keep = None if keep_ref is None else keep_ref[
        0, :, pl.ds(pl.multiple_of(ki * blk_k, blk_k), blk_k)]
    s = _masked_scores(q, k, scale, rel, qi, ki, blk_q, blk_k, causal,
                       q_base, k_base, window, keep)
    m_blk = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_blk)
    m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
    p = jnp.exp(s - m_safe)         # a masked entry: exp(NEG_INF - m) == 0
    corr = jnp.where(m <= NEG_INF, 0.0, jnp.exp(m - m_safe))
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * corr + _dot(p.astype(v.dtype), v)
    return m_new, l_new, acc_new

  m0 = jnp.full((blk_q, 1), NEG_INF, jnp.float32)
  l0 = jnp.zeros((blk_q, 1), jnp.float32)
  acc0 = jnp.zeros((blk_q, v_ref.shape[-1]), jnp.float32)   # the VALUES' width
  hi = _causal_k_hi(qi, q_base, k_base, blk_q, blk_k, n_kblocks) \
      if causal else n_kblocks
  lo = _window_k_lo(qi, q_base, k_base, blk_q, blk_k, window, n_kblocks) \
      if window is not None else 0
  m, l, acc = lax.fori_loop(lo, hi, body, (m0, l0, acc0))

  l_safe = jnp.where(l == 0.0, 1.0, l)
  o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
  lse_col = jnp.where(l == 0.0, NEG_INF, m + jnp.log(l_safe))  # [blk_q, 1]
  lse_ref[0] = jnp.broadcast_to(lse_col, (blk_q, LANES))


def _attn_bwd_dq_kernel(qb_ref, kb_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                        delta_ref, dq_ref, *, blk_q: int, blk_k: int,
                        kv_len: int, causal: bool, scale: float,
                        window=None):
  """dQ for one q-block: dQ = scale · Σ_k [P ⊙ (dO·Vᵀ − Δ)] · K."""
  qi = pl.program_id(1)
  q_base = qb_ref[0]
  k_base = kb_ref[0]
  q = q_ref[0]
  do = do_ref[0]                                    # [blk_q, D]
  lse = lse_ref[0][:, 0:1]                          # [blk_q, 1]
  delta = delta_ref[0][:, 0:1]                      # [blk_q, 1]
  n_kblocks = kv_len // blk_k
  rel = _rel(blk_q, blk_k) if causal else None

  def body(ki, dq):
    k = k_ref[0, pl.ds(ki * blk_k, blk_k), :]
    v = v_ref[0, pl.ds(ki * blk_k, blk_k), :]
    s = _masked_scores(q, k, scale, rel, qi, ki, blk_q, blk_k, causal,
                       q_base, k_base, window)
    _, ds = _pair_p_ds(s, lse, delta, do, v)
    return dq + _dot(ds.astype(k.dtype), k)

  dq0 = jnp.zeros((blk_q, q.shape[-1]), jnp.float32)
  hi = _causal_k_hi(qi, q_base, k_base, blk_q, blk_k, n_kblocks) \
      if causal else n_kblocks
  lo = _window_k_lo(qi, q_base, k_base, blk_q, blk_k, window, n_kblocks) \
      if window is not None else 0
  dq = lax.fori_loop(lo, hi, body, dq0)
  dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _attn_bwd_dkv_kernel(qb_ref, kb_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                         delta_ref, dk_ref, dv_ref, *, blk_q: int,
                         blk_k: int, q_len: int, causal: bool,
                         scale: float, window=None):
  """dK/dV for one k-block: dV = Σ_q Pᵀ·dO; dK = scale · Σ_q dSᵀ·Q."""
  ki = pl.program_id(1)
  q_base = qb_ref[0]
  k_base = kb_ref[0]
  k = k_ref[0]                                      # [blk_k, D]
  v = v_ref[0]
  n_qblocks = q_len // blk_q
  rel = _rel(blk_q, blk_k) if causal else None

  def body(qi, carry):
    dk, dv = carry
    q = q_ref[0, pl.ds(qi * blk_q, blk_q), :]
    do = do_ref[0, pl.ds(qi * blk_q, blk_q), :]
    lse = lse_ref[0, pl.ds(qi * blk_q, blk_q), 0:1]
    delta = delta_ref[0, pl.ds(qi * blk_q, blk_q), 0:1]
    s = _masked_scores(q, k, scale, rel, qi, ki, blk_q, blk_k, causal,
                       q_base, k_base, window)
    p, ds = _pair_p_ds(s, lse, delta, do, v)
    dv_new = dv + _dot(p.astype(do.dtype), do, _TN)
    dk_new = dk + _dot(ds.astype(q.dtype), q, _TN)
    return dk_new, dv_new

  dk0 = jnp.zeros((blk_k, k.shape[-1]), jnp.float32)
  dv0 = jnp.zeros((blk_k, v.shape[-1]), jnp.float32)
  lo = _causal_q_lo(ki, q_base, k_base, blk_q, blk_k) if causal else 0
  hi = _window_q_hi(ki, q_base, k_base, blk_q, blk_k, window, n_qblocks) \
      if window is not None else n_qblocks
  dk, dv = lax.fori_loop(lo, hi, body, (dk0, dv0))
  dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
  dv_ref[0] = dv.astype(dv_ref.dtype)


def _attn_bwd_fused_kernel(qb_ref, kb_ref, q_ref, k_ref, v_ref, do_ref,
                           lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, *,
                           blk_q: int, blk_k: int, q_len: int, causal: bool,
                           scale: float, window=None):
  """Single-pass backward: dK/dV for one k-block plus this k-block's dQ
  contributions, accumulated into a grid-resident full-sequence dQ output.

  The dQ output's index map ignores the k-grid index, so Mosaic keeps the
  block in VMEM across the sequential k steps (zeroed at ki == 0, flushed
  when the batch·head index advances). Scores/probabilities are computed
  once per (q, k) block pair instead of once in each of two kernels: 5
  MXU matmuls per pair vs 7 for the split backward.
  """
  ki = pl.program_id(1)
  q_base = qb_ref[0]
  k_base = kb_ref[0]
  k = k_ref[0]                                      # [blk_k, D]
  v = v_ref[0]
  n_qblocks = q_len // blk_q

  @pl.when(ki == 0)
  def _zero_dq():  # noqa: ANN202 - pallas region
    dq_ref[0] = jnp.zeros_like(dq_ref[0])

  rel = _rel(blk_q, blk_k) if causal else None

  def body(qi, carry):
    dk, dv = carry
    q = q_ref[0, pl.ds(qi * blk_q, blk_q), :]
    do = do_ref[0, pl.ds(qi * blk_q, blk_q), :]
    lse = lse_ref[0, pl.ds(qi * blk_q, blk_q), 0:1]
    delta = delta_ref[0, pl.ds(qi * blk_q, blk_q), 0:1]
    s = _masked_scores(q, k, scale, rel, qi, ki, blk_q, blk_k, causal,
                       q_base, k_base, window)
    p, ds = _pair_p_ds(s, lse, delta, do, v)
    ds = ds.astype(k.dtype)                 # one rounding serves dK and dQ
    dv_new = dv + _dot(p.astype(do.dtype), do, _TN)
    dk_new = dk + _dot(ds, q, _TN)
    prev = dq_ref[0, pl.ds(qi * blk_q, blk_q), :]
    dq_ref[0, pl.ds(qi * blk_q, blk_q), :] = prev + _dot(ds, k) * scale
    return dk_new, dv_new

  dk0 = jnp.zeros((blk_k, k.shape[-1]), jnp.float32)
  dv0 = jnp.zeros((blk_k, v.shape[-1]), jnp.float32)
  lo = _causal_q_lo(ki, q_base, k_base, blk_q, blk_k) if causal else 0
  hi = _window_q_hi(ki, q_base, k_base, blk_q, blk_k, window, n_qblocks) \
      if window is not None else n_qblocks
  dk, dv = lax.fori_loop(lo, hi, body, (dk0, dv0))
  dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
  dv_ref[0] = dv.astype(dv_ref.dtype)


def _attn_bwd_dkv_gqa_kernel(qb_ref, kb_ref, q_ref, k_ref, v_ref, do_ref,
                             lse_ref, delta_ref, dk_ref, dv_ref, *,
                             blk_q: int, blk_k: int, q_len: int,
                             causal: bool, scale: float, window=None):
  """Grouped-KV dK/dV: grid (b·kv_heads, n_kblocks, group).

  The group axis is INNERMOST, so each (blk_k, D) dK/dV block stays
  VMEM-resident while its g query heads sweep past, accumulating into it
  in f32 (assigned at qh == 0, read-modify-write after) — cross-head
  accumulation in the grid instead of expanding K/V g× through HBM and
  summing an expanded cotangent outside. Per-(q, k) block math is
  identical to :func:`_attn_bwd_dkv_kernel`; the q/do/lse/delta
  BlockSpecs select the current query head's row.
  """
  ki = pl.program_id(1)
  qh = pl.program_id(2)
  q_base = qb_ref[0]
  k_base = kb_ref[0]
  k = k_ref[0]                                      # [blk_k, D]
  v = v_ref[0]
  n_qblocks = q_len // blk_q
  rel = _rel(blk_q, blk_k) if causal else None

  def body(qi, carry):
    dk, dv = carry
    q = q_ref[0, pl.ds(qi * blk_q, blk_q), :]
    do = do_ref[0, pl.ds(qi * blk_q, blk_q), :]
    lse = lse_ref[0, pl.ds(qi * blk_q, blk_q), 0:1]
    delta = delta_ref[0, pl.ds(qi * blk_q, blk_q), 0:1]
    s = _masked_scores(q, k, scale, rel, qi, ki, blk_q, blk_k, causal,
                       q_base, k_base, window)
    p, ds = _pair_p_ds(s, lse, delta, do, v)
    return (dk + _dot(ds.astype(q.dtype), q, _TN),
            dv + _dot(p.astype(do.dtype), do, _TN))

  dk0 = jnp.zeros((blk_k, k.shape[-1]), jnp.float32)
  dv0 = jnp.zeros((blk_k, v.shape[-1]), jnp.float32)
  lo = _causal_q_lo(ki, q_base, k_base, blk_q, blk_k) if causal else 0
  hi = _window_q_hi(ki, q_base, k_base, blk_q, blk_k, window, n_qblocks) \
      if window is not None else n_qblocks
  dk, dv = lax.fori_loop(lo, hi, body, (dk0, dv0))
  dk = dk * scale

  @pl.when(qh == 0)
  def _assign():  # noqa: ANN202 - pallas region
    dk_ref[0] = dk
    dv_ref[0] = dv

  @pl.when(qh != 0)
  def _accumulate():  # noqa: ANN202 - pallas region
    dk_ref[0] = dk_ref[0] + dk
    dv_ref[0] = dv_ref[0] + dv


def _attn_bwd_fused_gqa_kernel(qb_ref, kb_ref, q_ref, k_ref, v_ref, do_ref,
                               lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, *,
                               blk_q: int, blk_k: int, q_len: int,
                               causal: bool, scale: float, window=None):
  """Grouped-KV single-pass backward: grid (b·kv_heads, group, n_kblocks).

  dQ of the current query head accumulates across the innermost k-block
  axis (zeroed at ki == 0) exactly like the MHA fused kernel. dK/dV are
  FULL [s_kv, D] f32 blocks resident across the whole (group, k-block)
  sweep; each step read-modify-writes only its blk_k-row slice, assigning
  at qh == 0 and accumulating after. VMEM ≈ (2·s_kv + s_q)·D·4B for the
  residents — :func:`_gqa_fused_fits` guards it and callers fall back to
  the split plan when it exceeds the budget.
  """
  qh = pl.program_id(1)
  ki = pl.program_id(2)
  q_base = qb_ref[0]
  k_base = kb_ref[0]
  k = k_ref[0]                                      # [blk_k, D]
  v = v_ref[0]
  n_qblocks = q_len // blk_q

  @pl.when(ki == 0)
  def _zero_dq():  # noqa: ANN202 - pallas region
    dq_ref[0] = jnp.zeros_like(dq_ref[0])

  rel = _rel(blk_q, blk_k) if causal else None

  def body(qi, carry):
    dk, dv = carry
    q = q_ref[0, pl.ds(qi * blk_q, blk_q), :]
    do = do_ref[0, pl.ds(qi * blk_q, blk_q), :]
    lse = lse_ref[0, pl.ds(qi * blk_q, blk_q), 0:1]
    delta = delta_ref[0, pl.ds(qi * blk_q, blk_q), 0:1]
    s = _masked_scores(q, k, scale, rel, qi, ki, blk_q, blk_k, causal,
                       q_base, k_base, window)
    p, ds = _pair_p_ds(s, lse, delta, do, v)
    ds = ds.astype(k.dtype)                 # one rounding serves dK and dQ
    dv_new = dv + _dot(p.astype(do.dtype), do, _TN)
    dk_new = dk + _dot(ds, q, _TN)
    prev = dq_ref[0, pl.ds(qi * blk_q, blk_q), :]
    dq_ref[0, pl.ds(qi * blk_q, blk_q), :] = prev + _dot(ds, k) * scale
    return dk_new, dv_new

  dk0 = jnp.zeros((blk_k, k.shape[-1]), jnp.float32)
  dv0 = jnp.zeros((blk_k, v.shape[-1]), jnp.float32)
  lo = _causal_q_lo(ki, q_base, k_base, blk_q, blk_k) if causal else 0
  hi = _window_q_hi(ki, q_base, k_base, blk_q, blk_k, window, n_qblocks) \
      if window is not None else n_qblocks
  dk, dv = lax.fori_loop(lo, hi, body, (dk0, dv0))
  dk = dk * scale

  sl = pl.ds(ki * blk_k, blk_k)

  @pl.when(qh == 0)
  def _assign():  # noqa: ANN202 - pallas region
    dk_ref[0, sl, :] = dk
    dv_ref[0, sl, :] = dv

  @pl.when(qh != 0)
  def _accumulate():  # noqa: ANN202 - pallas region
    dk_ref[0, sl, :] = dk_ref[0, sl, :] + dk
    dv_ref[0, sl, :] = dv_ref[0, sl, :] + dv


# VMEM budget for the grouped fused backward's resident blocks (dK+dV full
# f32 + dQ f32 + q/do); past this the split plan wins anyway because the
# residents crowd out double-buffering for the streamed blocks
GQA_FUSED_VMEM_BUDGET = 10 * 1024 * 1024


def _gqa_fused_fits(s_q: int, s_kv: int, d: int, itemsize: int) -> bool:
  resident = (2 * s_kv + s_q) * d * 4 + 2 * s_q * d * itemsize
  return resident <= GQA_FUSED_VMEM_BUDGET


# --- shared impl -----------------------------------------------------------


def _blocks(s_q, s_kv, blk_q, blk_k):
  """Clamp block sizes so any sequence length works without padding.

  Mosaic accepts a sublane block only if it is a multiple of 8 or equal
  to the full dimension, so shrink to the largest divisor of ``s`` that
  is a multiple of 8; when no such divisor exists (e.g. s = 2·499) fall
  back to one full-dimension block rather than a tiny degenerate one.
  """
  def _fit(blk, s):
    blk = min(blk, s)
    while blk > 0:
      if s % blk == 0 and (blk % 8 == 0 or blk == s):
        return blk
      blk -= 1
    return s
  return _fit(blk_q, s_q), _fit(blk_k, s_kv)


def _fold(x):
  b, s, h, d = x.shape
  return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unfold(x, b, h):
  bh, s, d = x.shape
  return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _base_arrays(q_base, kv_base):
  """Position bases as (1,)-shaped int32 scalar-prefetch operands.

  Traced scalars (ring attention derives them from ``lax.axis_index``)
  ride to the kernel through SMEM via ``PrefetchScalarGridSpec`` — 1-D
  blocked VMEM operands fail Mosaic layout verification on real TPUs.
  """
  qb = jnp.reshape(jnp.asarray(q_base, jnp.int32), (1,))
  kb = jnp.reshape(jnp.asarray(kv_base, jnp.int32), (1,))
  return qb, kb


def _group(q, k):
  """(kv_heads, group) from q/k head counts, validating divisibility."""
  h, hk = q.shape[2], k.shape[2]
  if h % hk:
    raise ValueError("kv heads (%d) must divide query heads (%d)"
                     % (hk, h))
  return hk, h // hk


def _kv_row_map(h, hk, g):
  """KV BlockSpec row for folded-query-row ``i``: query head i%h reads
  its group's KV head — the grouped-aware index map that lets the kernels
  consume unexpanded K/V (g == 1 degenerates to row i)."""
  return lambda i, j, *_: ((i // h) * hk + (i % h) // g, 0, 0)


def _q_row_map(h, hk, grp, qh_axis):
  """Query-row BlockSpec map for the grouped (b·hk)-rooted grids: grid
  dim 0 is the folded KV row, grid dim ``qh_axis`` the head-in-group
  position; the map selects that query head's folded row. ONE definition
  for both grouped backward plans so the blocked grouping convention
  (KV head j serves query heads [j·g, (j+1)·g)) cannot drift between
  them."""
  def _map(*idx):
    i, qh = idx[0], idx[qh_axis]
    return ((i // hk) * h + (i % hk) * grp + qh, 0, 0)
  return _map


def _check_window(window, causal):
  if window is None:
    return None
  window = int(window)
  if window < 1:
    raise ValueError("window must be >= 1, got %d" % window)
  if not causal:
    raise ValueError("sliding-window attention requires causal=True "
                     "(the window is 'the last W positions')")
  return window


@functools.partial(jax.jit, static_argnames=("causal", "blk_q", "blk_k",
                                             "interpret", "window", "scale"))
def _fwd_impl(q, k, v, q_base, kv_base, causal, blk_q, blk_k, interpret,
              window=None, scale=None, keep=None):
  """``keep`` (``[b, s_q, s_kv]`` int8, nonzero = attend; None = none): a
  mask BY QUERY beside the positional one, shared by the heads through its
  index map (head ``i``'s block is batch row ``i // h``'s); FORWARD only."""
  b, s_q, h, d = q.shape
  s_kv, dv = k.shape[1], v.shape[3]     # values may be another width (forward)
  hk, g = _group(q, k)
  blk_q, blk_k = _blocks(s_q, s_kv, blk_q, blk_k)
  if scale is None:
    scale = 1.0 / (d ** 0.5)
  qf, kf, vf = _fold(q), _fold(k), _fold(v)
  qb, kb = _base_arrays(q_base, kv_base)

  kernel = functools.partial(_attn_fwd_kernel, blk_q=blk_q, blk_k=blk_k,
                             kv_len=s_kv, causal=causal, scale=scale,
                             window=_check_window(window, causal))
  out, lse = pl.pallas_call(
      kernel,
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=2,
          grid=(b * h, s_q // blk_q),
          in_specs=[
              pl.BlockSpec((1, blk_q, d), lambda i, j, *_: (i, j, 0)),
              pl.BlockSpec((1, s_kv, d), _kv_row_map(h, hk, g)),
              pl.BlockSpec((1, s_kv, dv), _kv_row_map(h, hk, g)),
          ] + ([] if keep is None else [
              pl.BlockSpec((1, blk_q, s_kv), lambda i, j, *_: (i // h, j, 0))]),
          out_specs=[
              pl.BlockSpec((1, blk_q, dv), lambda i, j, *_: (i, j, 0)),
              pl.BlockSpec((1, blk_q, LANES), lambda i, j, *_: (i, j, 0)),
          ],
      ),
      out_shape=[
          jax.ShapeDtypeStruct((b * h, s_q, dv), q.dtype),
          jax.ShapeDtypeStruct((b * h, s_q, LANES), jnp.float32),
      ],
      interpret=interpret,
  )(qb, kb, qf, kf, vf, *(() if keep is None else (keep.astype(jnp.int8),)))

  return _unfold(out, b, h), lse[:, :, 0].reshape(b, h, s_q)


# The backward prefers different tiles than the forward (v5e fetch-timed
# sweeps at b4 s4096 h8 d128): the fused single-pass kernel wants smaller
# q-blocks — each (q,k) pair read-modify-writes a blk_q-row slice of the
# resident dQ accumulator, and 128 rows keeps that RMW on the critical
# path shorter — while the split kernels match the forward's (256, 512).
# Resolved per-mode inside _bwd_impl — AFTER its VMEM fallback may have
# switched fused→split, so a fallback under default tuning picks up the
# split plan's blocks (an early comparison against the fused defaults
# would miss whenever _blocks had already clamped them for short
# sequences). Override with blk_bwd_q/blk_bwd_k (kept None = defaults).
DEFAULT_BWD_BLOCKS = {"fused": (128, 512), "split": (256, 512)}


def _default_bwd_blocks(bwd, d):
  """``DEFAULT_BWD_BLOCKS[bwd]`` at head_dim ``d``. The fused plan's 128
  rows were swept at head_dim 128, where the slice of the resident float32
  dQ that a pair read-modify-writes is 64 KB; a narrower head takes the rows
  that make the same 64 KB, up to 256 (head_dim 64: v5e, 16 x 1024 x 12
  heads bf16, forward + backward 3.91 -> 3.59 ms a call, where 128 x 256,
  256 x 256 and 64 x 512 read 4.63, 3.98 and 5.68: a pair costs about 0.4 us
  whatever its size, so fewer and larger pairs win)."""
  blk_q, blk_k = DEFAULT_BWD_BLOCKS[bwd]
  if bwd == "fused":
    blk_q = min(256, max(blk_q, blk_q * 128 // d))
  return blk_q, blk_k


def _resolve_bwd(bwd):
  """Validate/default the backward mode (block tuning resolves later,
  see DEFAULT_BWD_BLOCKS)."""
  bwd = bwd or "fused"
  if bwd not in DEFAULT_BWD_BLOCKS:
    raise ValueError("bwd must be 'fused' or 'split', got %r" % (bwd,))
  return bwd


@functools.partial(jax.jit, static_argnames=("causal", "blk_q", "blk_k",
                                             "interpret", "bwd", "window"))
def _bwd_impl(q, k, v, out, lse, g, g_lse, q_base, kv_base, causal, blk_q,
              blk_k, interpret, bwd="fused", window=None):
  window = _check_window(window, causal)
  b, s_q, h, d = q.shape
  if v.shape[3] != d:
    raise ValueError(
        "the flash BACKWARD kernels take one head_dim for q, k and v, got "
        "keys of %d and values of %d dims: only the forward is built for "
        "such heads (train them through the dense attention)"
        % (d, v.shape[3]))
  s_kv = k.shape[1]
  hk, grp = _group(q, k)
  scale = 1.0 / (d ** 0.5)
  qf, of, gf = (_fold(x) for x in (q, out, g))
  kf, vf = _fold(k), _fold(v)
  qb, kb = _base_arrays(q_base, kv_base)

  # Δ_i = Σ_d dO·O  (+ the lse cotangent folds in with opposite sign:
  # dS = P ⊙ (dP − Δ + g_lse))
  delta = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32), axis=-1)
  if g_lse is not None:
    delta = delta - g_lse.reshape(b * h, s_q)
  # lse/Δ enter the kernels lane-broadcast (see LANES)
  lse_f = jnp.broadcast_to(lse.reshape(b * h, s_q)[:, :, None],
                           (b * h, s_q, LANES))
  delta = jnp.broadcast_to(delta[:, :, None], (b * h, s_q, LANES))

  full3 = lambda i, j, *_: (i, 0, 0)      # noqa: E731
  row3 = lambda i, j, *_: (i, j, 0)       # noqa: E731
  kvfull = _kv_row_map(h, hk, grp)        # query row i -> its KV head's row

  if bwd == "fused" and grp > 1 and not _gqa_fused_fits(
      s_q, s_kv, d, q.dtype.itemsize):
    bwd = "split"   # resident dK/dV would not fit VMEM; split plan wins
  # block defaults resolve AFTER the fallback so a fused→split switch
  # gets split tuning; explicit caller overrides (non-None) are untouched
  dq_def, dk_def = _default_bwd_blocks(bwd, d)
  blk_q = dq_def if blk_q is None else blk_q
  blk_k = dk_def if blk_k is None else blk_k
  blk_q, blk_k = _blocks(s_q, s_kv, blk_q, blk_k)

  if bwd == "fused" and grp > 1:
    qrow = _q_row_map(h, hk, grp, qh_axis=1)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_attn_bwd_fused_gqa_kernel, blk_q=blk_q,
                          blk_k=blk_k, q_len=s_q, causal=causal,
                          scale=scale, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b * hk, grp, s_kv // blk_k),
            in_specs=[
                pl.BlockSpec((1, s_q, d), qrow),
                pl.BlockSpec((1, blk_k, d),
                             lambda i, qh, ki, *_: (i, ki, 0)),
                pl.BlockSpec((1, blk_k, d),
                             lambda i, qh, ki, *_: (i, ki, 0)),
                pl.BlockSpec((1, s_q, d), qrow),
                pl.BlockSpec((1, s_q, LANES), qrow),
                pl.BlockSpec((1, s_q, LANES), qrow),
            ],
            out_specs=[
                pl.BlockSpec((1, s_q, d), qrow),    # dQ: resident across ki
                pl.BlockSpec((1, s_kv, d),
                             lambda i, qh, ki, *_: (i, 0, 0)),
                pl.BlockSpec((1, s_kv, d),
                             lambda i, qh, ki, *_: (i, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_q, d), jnp.float32),
            jax.ShapeDtypeStruct((b * hk, s_kv, d), jnp.float32),
            jax.ShapeDtypeStruct((b * hk, s_kv, d), jnp.float32),
        ],
        interpret=interpret,
    )(qb, kb, qf, kf, vf, gf, lse_f, delta)
    return (_unfold(dq, b, h).astype(q.dtype),
            _unfold(dk, b, hk).astype(k.dtype),
            _unfold(dv, b, hk).astype(v.dtype))

  if bwd == "fused":
    dq, dk, dv = pl.pallas_call(
        functools.partial(_attn_bwd_fused_kernel, blk_q=blk_q, blk_k=blk_k,
                          q_len=s_q, causal=causal, scale=scale, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b * h, s_kv // blk_k),
            in_specs=[
                pl.BlockSpec((1, s_q, d), full3),
                pl.BlockSpec((1, blk_k, d), row3),
                pl.BlockSpec((1, blk_k, d), row3),
                pl.BlockSpec((1, s_q, d), full3),
                pl.BlockSpec((1, s_q, LANES), full3),
                pl.BlockSpec((1, s_q, LANES), full3),
            ],
            out_specs=[
                pl.BlockSpec((1, s_q, d), full3),   # dQ: resident across ki
                pl.BlockSpec((1, blk_k, d), row3),
                pl.BlockSpec((1, blk_k, d), row3),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_q, d), jnp.float32),
            jax.ShapeDtypeStruct((b * h, s_kv, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, s_kv, d), v.dtype),
        ],
        interpret=interpret,
    )(qb, kb, qf, kf, vf, gf, lse_f, delta)
    return (_unfold(dq, b, h).astype(q.dtype), _unfold(dk, b, h),
            _unfold(dv, b, h))

  dq = pl.pallas_call(
      functools.partial(_attn_bwd_dq_kernel, blk_q=blk_q, blk_k=blk_k,
                        kv_len=s_kv, causal=causal, scale=scale, window=window),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=2,
          grid=(b * h, s_q // blk_q),
          in_specs=[
              pl.BlockSpec((1, blk_q, d), row3),
              pl.BlockSpec((1, s_kv, d), kvfull),
              pl.BlockSpec((1, s_kv, d), kvfull),
              pl.BlockSpec((1, blk_q, d), row3),
              pl.BlockSpec((1, blk_q, LANES), row3),
              pl.BlockSpec((1, blk_q, LANES), row3),
          ],
          out_specs=pl.BlockSpec((1, blk_q, d), row3),
      ),
      out_shape=jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
      interpret=interpret,
  )(qb, kb, qf, kf, vf, gf, lse_f, delta)

  if grp > 1:
    qrow = _q_row_map(h, hk, grp, qh_axis=2)
    dk, dv = pl.pallas_call(
        functools.partial(_attn_bwd_dkv_gqa_kernel, blk_q=blk_q,
                          blk_k=blk_k, q_len=s_q, causal=causal,
                          scale=scale, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b * hk, s_kv // blk_k, grp),
            in_specs=[
                pl.BlockSpec((1, s_q, d), qrow),
                pl.BlockSpec((1, blk_k, d),
                             lambda i, ki, qh, *_: (i, ki, 0)),
                pl.BlockSpec((1, blk_k, d),
                             lambda i, ki, qh, *_: (i, ki, 0)),
                pl.BlockSpec((1, s_q, d), qrow),
                pl.BlockSpec((1, s_q, LANES), qrow),
                pl.BlockSpec((1, s_q, LANES), qrow),
            ],
            out_specs=[
                # resident across the innermost group sweep
                pl.BlockSpec((1, blk_k, d),
                             lambda i, ki, qh, *_: (i, ki, 0)),
                pl.BlockSpec((1, blk_k, d),
                             lambda i, ki, qh, *_: (i, ki, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b * hk, s_kv, d), jnp.float32),
            jax.ShapeDtypeStruct((b * hk, s_kv, d), jnp.float32),
        ],
        interpret=interpret,
    )(qb, kb, qf, kf, vf, gf, lse_f, delta)
    return (_unfold(dq, b, h), _unfold(dk, b, hk).astype(k.dtype),
            _unfold(dv, b, hk).astype(v.dtype))

  dk, dv = pl.pallas_call(
      functools.partial(_attn_bwd_dkv_kernel, blk_q=blk_q, blk_k=blk_k,
                        q_len=s_q, causal=causal, scale=scale, window=window),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=2,
          grid=(b * h, s_kv // blk_k),
          in_specs=[
              pl.BlockSpec((1, s_q, d), full3),
              pl.BlockSpec((1, blk_k, d), row3),
              pl.BlockSpec((1, blk_k, d), row3),
              pl.BlockSpec((1, s_q, d), full3),
              pl.BlockSpec((1, s_q, LANES), full3),
              pl.BlockSpec((1, s_q, LANES), full3),
          ],
          out_specs=[
              pl.BlockSpec((1, blk_k, d), row3),
              pl.BlockSpec((1, blk_k, d), row3),
          ],
      ),
      out_shape=[
          jax.ShapeDtypeStruct((b * h, s_kv, d), k.dtype),
          jax.ShapeDtypeStruct((b * h, s_kv, d), v.dtype),
      ],
      interpret=interpret,
  )(qb, kb, qf, kf, vf, gf, lse_f, delta)

  return _unfold(dq, b, h), _unfold(dk, b, h), _unfold(dv, b, h)


# --- public: full attention -------------------------------------------------


def flash_attention(q, k, v, causal: bool = True, blk_q: int = 256,
                    blk_k: int = 512, interpret: bool = False,
                    bwd: str = None, blk_bwd_q: int = None,
                    blk_bwd_k: int = None, window: int = None, keep=None):
  """Fused (self-)attention with fused backward. q: [batch, seq, heads,
  head_dim]; k/v: same, or with heads/g KV heads (grouped-query
  attention — consumed unexpanded, see module docstring); seq must
  divide by the (clamped) block sizes. ``bwd``: 'fused' (single-pass
  dQ/dK/dV, the default) or 'split' (two kernels). The backward uses its
  own block sizes (``DEFAULT_BWD_BLOCKS`` per mode, the fused plan's q-block
  by head_dim: ``_default_bwd_blocks``; unless overridden).
  ``window``
  (requires causal) restricts each query to its last ``window``
  positions (sliding-window attention); the kernels' block loops bound
  to the window, so attention FLOPs become O(seq·window) instead of
  O(seq²). ``keep`` (``[batch, seq, seq]``, nonzero = attend) is a mask BY
  QUERY beside the causal one, shared by the heads: the FORWARD takes it, a
  gradient through it is refused by name (:func:`_keep_refusal`)."""
  if keep is not None:
    return _flash_keep(q, k, v, keep, 0, 0, causal, blk_q, blk_k, interpret,
                       _check_window(window, causal))[0]
  return _flash_vjp(q, k, v, causal, blk_q, blk_k, interpret,
                    _resolve_bwd(bwd), blk_bwd_q, blk_bwd_k, window)


def _keep_refusal():
  return ValueError(
      "the flash backward takes no keep operand: attention under a mask by "
      "query (a learned selection of the cached tokens, "
      "TransformerConfig.sparse_topk) has the FORWARD kernel only, and a "
      "gradient through the selection is not built")


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _flash_keep(q, k, v, keep, q_base, kv_base, causal, blk_q, blk_k,
                interpret, window):
  """The forward (a whole attention, or a block partial at traced bases)
  under a keep operand: ``(out, lse)``. Its VJP raises."""
  return _fwd_impl(q, k, v, q_base, kv_base, causal, blk_q, blk_k, interpret,
                   window, None, keep)


def _flash_keep_fwd(q, k, v, keep, q_base, kv_base, causal, blk_q, blk_k,
                    interpret, window):
  return _flash_keep(q, k, v, keep, q_base, kv_base, causal, blk_q, blk_k,
                     interpret, window), None


def _flash_keep_bwd(causal, blk_q, blk_k, interpret, window, residuals,
                    cotangents):
  raise _keep_refusal()


_flash_keep.defvjp(_flash_keep_fwd, _flash_keep_bwd)


def flash_attention_sharded(q, k, v, mesh, causal: bool = True,
                            interpret: bool = False, window: int = None,
                            batch_axes=None):
  """:func:`flash_attention` applied per shard through shard_map.

  For attention inside a GSPMD-partitioned model whose sequence is NOT
  mesh-sharded (that case is ``parallel.ring_attention``): the TPU compiler
  refuses to partition a Mosaic kernel on its own ("Mosaic kernels cannot
  be automatically partitioned"), so the kernel is mapped over the shards
  it is embarrassingly parallel in — batch over the data(+fsdp) axes, heads
  over the tensor axis. Heads shard only when BOTH the query and the KV
  head counts divide the tensor axis: the grouped kernel maps query head
  ``i`` to KV head ``i // g`` locally, which needs the two laid out alike;
  otherwise heads stay whole (replicated over tensor) on every shard.
  """
  from jax.sharding import PartitionSpec as P
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib
  from tensorflowonspark_tpu.utils.compat import jax_shard_map as shard_map

  if batch_axes is None:
    batch_axes = mesh_lib.data_axes(mesh)
  t = mesh.shape.get(mesh_lib.AXIS_TENSOR, 1)
  heads_axis = mesh_lib.AXIS_TENSOR \
      if t > 1 and q.shape[2] % t == 0 and k.shape[2] % t == 0 else None
  spec = P(batch_axes or None, None, heads_axis, None)
  fn = shard_map(
      lambda qq, kk, vv: flash_attention(qq, kk, vv, causal=causal,
                                         interpret=interpret, window=window),
      mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
      check_vma=False)
  return fn(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash_vjp(q, k, v, causal, blk_q, blk_k, interpret, bwd, blk_bwd_q,
               blk_bwd_k, window):
  out, _ = _fwd_impl(q, k, v, 0, 0, causal, blk_q, blk_k, interpret,
                     window)
  return out


def _flash_fwd(q, k, v, causal, blk_q, blk_k, interpret, bwd, blk_bwd_q,
               blk_bwd_k, window):
  out, lse = _fwd_impl(q, k, v, 0, 0, causal, blk_q, blk_k, interpret,
                       window)
  return out, (q, k, v, out, lse)


def _flash_bwd(causal, blk_q, blk_k, interpret, bwd, blk_bwd_q, blk_bwd_k,
               window, residuals, g):
  q, k, v, out, lse = residuals
  return _bwd_impl(q, k, v, out, lse, g, None, 0, 0, causal, blk_bwd_q,
                   blk_bwd_k, interpret, bwd, window)


_flash_vjp.defvjp(_flash_fwd, _flash_bwd)


# --- public: ring-composable block partial ----------------------------------


def flash_attention_block(q, k, v, q_base, kv_base, causal: bool = True,
                          blk_q: int = 256, blk_k: int = 512,
                          interpret: bool = False, bwd: str = None,
                          blk_bwd_q: int = None, blk_bwd_k: int = None,
                          window: int = None, scale: float = None,
                          keep=None):
  """Partial attention of local queries against ONE KV block.

  q: [B, Sq, H, D] at absolute positions ``q_base + arange(Sq)``;
  k/v: [B, Sk, H, D] — or [B, Sk, H/g, D] grouped (GQA), consumed
  unexpanded — at ``kv_base + arange(Sk)`` (bases may be traced —
  inside shard_map they depend on ``lax.axis_index``). Returns
  (normalized partial output, logsumexp) — merge partials across blocks
  with :func:`merge_partials`. Differentiable in q/k/v (including through
  the lse output). ``window`` composes with the ring: a KV block entirely
  behind the window collapses to zero loop iterations (the bounds are
  computed from the traced bases), so out-of-window ring steps cost only
  the kernel launch and the merge. ``scale`` is the softmax scale where it
  is not ``head_dim^-0.5`` (a latent layer under YaRN): FORWARD only, the
  backward kernels keep the default scale and are not reached. ``keep``
  (``[B, Sq, Sk]``, nonzero = attend: this block's columns of a mask by
  query, shared by the heads) likewise: forward only, its VJP raises.
  """
  if keep is not None:
    if scale is not None:
      raise ValueError("a keep operand beside a softmax scale is not built")
    return _flash_keep(q, k, v, keep, q_base, kv_base, causal, blk_q, blk_k,
                       interpret, _check_window(window, causal))
  if scale is not None:
    return _fwd_impl(q, k, v, q_base, kv_base, causal, blk_q, blk_k,
                     interpret, window, scale)
  return _flash_block_vjp(q, k, v, q_base, kv_base, causal, blk_q, blk_k,
                          interpret, _resolve_bwd(bwd), blk_bwd_q,
                          blk_bwd_k, window)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12))
def _flash_block_vjp(q, k, v, q_base, kv_base, causal, blk_q, blk_k,
                     interpret, bwd, blk_bwd_q, blk_bwd_k, window):
  return _fwd_impl(q, k, v, q_base, kv_base, causal, blk_q, blk_k,
                   interpret, window)


def _flash_block_fwd(q, k, v, q_base, kv_base, causal, blk_q, blk_k,
                     interpret, bwd, blk_bwd_q, blk_bwd_k, window):
  out, lse = _fwd_impl(q, k, v, q_base, kv_base, causal, blk_q, blk_k,
                       interpret, window)
  return (out, lse), (q, k, v, out, lse, q_base, kv_base)


def _flash_block_bwd(causal, blk_q, blk_k, interpret, bwd, blk_bwd_q,
                     blk_bwd_k, window, residuals, cotangents):
  q, k, v, out, lse, q_base, kv_base = residuals
  g, g_lse = cotangents
  dq, dk, dv = _bwd_impl(q, k, v, out, lse, g, g_lse, q_base, kv_base,
                         causal, blk_bwd_q, blk_bwd_k, interpret, bwd,
                         window)
  zero_base = np.zeros((), jax.dtypes.float0)
  return dq, dk, dv, zero_base, zero_base


_flash_block_vjp.defvjp(_flash_block_fwd, _flash_block_bwd)


def merge_partials(o_a, lse_a, o_b, lse_b):
  """Combine two normalized attention partials (the ring-merge step).

  Given partial outputs over disjoint KV sets with their logsumexps,
  produces the exact partial over the union. Fully-masked partials
  (lse = NEG_INF) contribute nothing.
  """
  lse_new = jnp.logaddexp(lse_a, lse_b)               # [B, H, S]
  lse_safe = jnp.where(lse_new <= NEG_INF, 0.0, lse_new)
  w_a = jnp.where((lse_a <= NEG_INF)[..., None], 0.0,
                  jnp.exp(lse_a - lse_safe)[..., None])
  w_b = jnp.where((lse_b <= NEG_INF)[..., None], 0.0,
                  jnp.exp(lse_b - lse_safe)[..., None])
  # weights are [B,H,S,1]; outputs are [B,S,H,D]
  w_a = jnp.swapaxes(w_a, 1, 2)
  w_b = jnp.swapaxes(w_b, 1, 2)
  o = o_a.astype(jnp.float32) * w_a + o_b.astype(jnp.float32) * w_b
  return o.astype(o_a.dtype), lse_new
