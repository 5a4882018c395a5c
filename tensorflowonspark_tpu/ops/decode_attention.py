"""One token a slot against that slot's LIVE cache rows: the serving decode
step's attention as a kernel that stops at each slot's cursor.

``cached_k`` / ``cached_v`` ``[b, max, kv_heads * d]`` are two leaves of the
KV slab in their stored, folded, lane-dense layout, ``lengths [b]`` each
slot's cursor: rows below it are the slot's context, rows at and above it
were never written or belong to a finished request. The dense path
(``models.transformer._cached_attention``, which keeps every input this
kernel does not take) contracts the query against all ``max`` rows and masks
afterwards: three quarters of the K/V bytes a GPT-2 serving step read were
rows under a mask of ``-1e30`` (PERF.md section 6, PR 31).

The kernel never sees a leaf whole. Both stay in HBM; for slot ``i`` the
``cdiv(lengths[i], block)`` blocks of ``block`` rows that hold live rows are
brought into VMEM by hand, double-buffered, the first block of the NEXT slot
starting behind the last of this one (one chain of DMAs over all slots: a
slot's first block would otherwise wait out its latency alone, 16 times a
call). A slot at cursor 0 reads nothing. Past the cursor the last block's
scores are masked and its V rows zeroed (0 x NaN is NaN: nothing above the
cursor may reach the sum).

The mathematics is the dense path's at its precision. Scores are ONE
contraction of the query, expanded block-diagonally over the KV heads
(query head ``i`` in KV head ``i // g``'s ``d`` lanes), against the block
as stored, bf16 x bf16 accumulated in f32; an online softmax (running max,
sum and output in f32) starts from the step's own key and value, which the
cache does not hold yet, so there is one softmax over cache and own part;
the probabilities go into V as three bf16 terms that sum to the f32 number
(three exact MXU passes, what ``transformer._cache_contract`` does). Each
head's own ``d`` lanes of ``probs @ V`` are the output.

A leaf may be a RING (``TransformerConfig.kv_ring``: a window layer's last
rows, position ``p`` in row ``p % max``): its live rows are still the rows
below ``lengths`` (``min(cursor, max)``), in whatever order, but for a run of
them that the window excludes, foremost the row the step is about to
overwrite. ``skip [2, b]`` names that cyclic run a slot (first row, count);
its scores are masked as the rows past the cursor are.

The VALUES may have another width than the keys (``cached_v [b, max, kv_heads
* dv]``, keys of 192 against values of 128): the scores want only the K leaf's
minor axis in whole lanes (one contraction against the block as stored), the
output folds onto the values' lanes. ``sink [h]`` is a learned scalar a query
head that joins the softmax's DENOMINATOR and nothing else: it opens the
online softmax beside the step's own key, as one more score whose value is
zero (so it is counted once, whatever the number of blocks).

K and V may be the SAME leaf (a latent cache, ``models/mla.py``, whose row is
the shared key of every head and, in its first lanes, their shared value):
the caller hands it as both and keeps the output's lanes that are values.
``scale`` replaces ``d^-0.5`` where the scores' scale is not the contracted
width's (a latent's 640 lanes stand for keys of 192).

A SELECTION (``TransformerConfig.sparse_topk``: a learned indexer chose the
cached tokens each slot's query attends) comes as ``keep``: one row a slot
over the leaf's positions, held whole in VMEM as ``[b, max / block, block]``
int32 (a slot's row of 32768 positions is 128 KB), and one scalar a slot for
the step's own token, which the selection may drop like any other. A block's
row of the mask joins the cursor's mask on the scores; the own token then
opens the softmax with weight 0 (its score still anchors the running max).
The kernel brings the same blocks as without a selection: rows chosen by a
seeded indexer lie in every block, so there is none to skip.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: lanes of a vreg
LANES = 128
#: rows of K and of V a DMA brings: wide enough that a block's compute
#: (0.6 us whatever its rows) hides behind its DMA, narrow enough that the
#: rows read past a cursor (half a block a slot) stay few beside the live
#: ones. On the chip at the GPT-2 cells' cursors: 32 rows 109 us a call, 64
#: 67, 128 50, 256 55, 512 73 (PERF.md section 6, PR 31)
BLOCK = 128
#: what the kernel may hold in VMEM (:func:`_vmem_bytes`; the GPT-2 cells'
#: 16 slots x 20 heads x 1280 lanes: 4.8 MB)
VMEM_BUDGET = 24 << 20


def _padded_heads(h: int) -> int:
  """Query rows as the kernel holds them: whole packed bf16 sublane tiles."""
  return -(-h // 16) * 16


def _vmem_bytes(b: int, h: int, dv: int, c: int, cv: int,
                keep_rows: int = 0) -> int:
  """K and V blocks double-buffered, every slot's expanded query, own key
  and value and folded output, the f32 output rows and the three-term
  product of one slot (``c`` the K leaf's lanes, ``cv`` the V leaf's), and a
  selection's keep rows (``keep_rows`` positions a slot, int32)."""
  hp = _padded_heads(h)
  return (2 * BLOCK * (c + cv) * 2 + b * ((hp + 16) * c + 16 * cv) * 2
          + b * hp * max(dv, LANES) * 4 + (1 + 3) * hp * cv * 4
          + b * keep_rows * 4)


def supports(q_shape, q_dtype, cache_shape, cache_dtype,
             v_shape=None, keep: bool = False) -> bool:
  """Whether :func:`decode_attention` can take queries ``[b, h, d]`` over
  cache leaves ``[b, max, kv_heads * d]`` (K) and ``v_shape`` ``[b, max,
  kv_heads * dv]`` (V; None = the K leaf's): both bf16, each minor axis whole
  lanes, the position axis whole blocks, a VALUE head's ``dv`` lanes a
  divisor or a multiple of a vreg's 128 (the output leaves the kernel folded
  onto ``max(dv, 128)`` lanes; the keys' ``d`` is only contracted over), whole
  query groups, and the blocks (with ``keep``, a selection's mask rows too) in
  VMEM."""
  v_shape = cache_shape if v_shape is None else v_shape
  if len(q_shape) != 3 or len(cache_shape) != 3 or len(v_shape) != 3:
    return False
  b, h, d = q_shape
  _, mx, c = cache_shape
  cv = v_shape[2]
  if c % d or cv % (c // d) or tuple(v_shape[:2]) != (b, mx):
    return False
  hk = c // d
  dv = cv // hk
  return (jnp.dtype(q_dtype) == jnp.bfloat16
          and jnp.dtype(cache_dtype) == jnp.bfloat16
          and cache_shape[0] == b and c % LANES == 0 and cv % LANES == 0
          and h % hk == 0 and (dv % LANES == 0 or LANES % dv == 0)
          and mx % BLOCK == 0
          and _vmem_bytes(b, h, dv, c, cv, mx if keep else 0) <= VMEM_BUDGET)


def _kernel(len_ref, *refs, g, d, scale, ring, sunk, kept=False):
  skip_ref = refs[0] if ring else None      # [2 * slots]: first rows, counts
  refs = refs[1:] if ring else refs
  own_ref = refs[0] if kept else None       # [slots]: the own token stays
  refs = refs[1:] if kept else refs
  sink_ref = refs[0] if sunk else None      # [hp, LANES] f32, lanes alike
  refs = refs[1:] if sunk else refs
  keep_ref = refs[0] if kept else None      # [slots, max / block, block] i32
  (q_ref, k_own_ref, v_own_ref, k_hbm, v_hbm, o_ref,
   k_buf, v_buf, acc, sem) = refs[1:] if kept else refs
  slots, mx = k_hbm.shape[:2]
  hp, c = acc.shape          # the V leaf's lanes; d a VALUE head's
  w = o_ref.shape[2]
  block = BLOCK

  def live(slot):
    """(live rows of a slot, the blocks that hold them)."""
    n = jnp.clip(len_ref[slot], 0, mx)
    return n, (n + block - 1) // block

  def copies(slot, j, buf):
    rows = pl.ds(pl.multiple_of(j * block, block), block)
    return (pltpu.make_async_copy(k_hbm.at[slot, rows], k_buf.at[buf],
                                  sem.at[0, buf]),
            pltpu.make_async_copy(v_hbm.at[slot, rows], v_buf.at[buf],
                                  sem.at[1, buf]))

  def start(slot, j, buf):
    for copy in copies(slot, j, buf):
      copy.start()

  @pl.when(live(0)[1] > 0)
  def _():
    start(0, 0, 0)

  # row i keeps KV head i // g's d lanes of probs @ V; the rest (other
  # heads' V under this head's probabilities) goes
  lane = jax.lax.broadcasted_iota(jnp.int32, (hp, c), 1)
  lo = (jax.lax.broadcasted_iota(jnp.int32, (hp, c), 0) // g) * d
  own = jnp.logical_and(lane >= lo, lane < lo + d)

  def fold(x):
    """A row's own lanes of ``x [hp, c]``, folded onto the output's: whole
    vregs, so a head of 64 lanes leaves in its half of 128."""
    kept = jnp.where(own, x, 0.0)
    return functools.reduce(
        jnp.add, [kept[:, t * w:(t + 1) * w] for t in range(c // w)])

  def one_slot(i, first):
    """Slot i, whose blocks are the call's ``first``-th on: block t of the
    call (counted over all slots) lands in buffer t % 2 and is started
    while block t - 1 is computed on, across slots too."""
    n, blocks = live(i)
    nxt = jnp.minimum(i + 1, slots - 1)
    next_blocks = jnp.where(i + 1 < slots, live(nxt)[1], 0)

    @pl.when(jnp.logical_and(blocks == 0, next_blocks > 0))
    def _():
      start(nxt, 0, first % 2)

    v_own = jnp.broadcast_to(v_own_ref[i].astype(jnp.float32), (hp, c))

    def opening():
      """(q, max, sum) as the step's own key opens the softmax: max = its
      score, sum = 1, output = its value; beside a SINK, one more score
      whose value is zero, max = the larger of the two."""
      q = q_ref[i]                                      # [hp, c_k] bf16
      s_own = jnp.sum(
          q.astype(jnp.float32) * k_own_ref[i].astype(jnp.float32),
          axis=-1, keepdims=True) * scale                # [hp, 1]
      if kept:
        # a selection may have dropped the own token: weight 0, its score
        # still the running max's start (scores are a few units apart)
        stays = (own_ref[i] > 0).astype(jnp.float32)
        acc[...] = v_own * stays
        return q, s_own, jnp.zeros_like(s_own) + stays
      if not sunk:
        acc[...] = v_own
        return q, s_own, jnp.ones_like(s_own)
      b_h = sink_ref[...][:, :1]
      m = jnp.maximum(s_own, b_h)
      acc[...] = v_own * jnp.exp(s_own - m)
      return q, m, jnp.exp(s_own - m) + jnp.exp(b_h - m)

    @pl.when(blocks == 0)
    def _():                  # nothing cached: the token attends itself
      if sunk:                # and the sink takes its share of that
        total = opening()[2]
        o_ref[i] = fold(acc[...] / total)
      else:
        o_ref[i] = fold(v_own)

    @pl.when(blocks > 0)
    def _():
      q, m_own, l_own = opening()

      def one_block(j, carry):
        m, l = carry
        buf = (first + j) % 2

        @pl.when(j + 1 < blocks)
        def _():
          start(i, j + 1, 1 - buf)

        @pl.when(jnp.logical_and(j + 1 == blocks, next_blocks > 0))
        def _():
          start(nxt, 0, 1 - buf)

        k_copy, v_copy = copies(i, j, buf)
        k_copy.wait()
        s = jax.lax.dot_general(q, k_buf[buf], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        left = n - j * block                             # live rows in here
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = col < left
        if ring:
          # rows [first, first + count) of the ring, cyclically, are outside
          # the window
          behind = col + (j * block - skip_ref[i])
          behind = jnp.where(behind < 0, behind + mx, behind)
          keep = jnp.logical_and(keep, behind >= skip_ref[slots + i])
        if kept:
          keep = jnp.logical_and(keep, keep_ref[i, pl.ds(j, 1), :] > 0)
        s = jnp.where(keep, s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha, p = jnp.exp(m - m_new), jnp.exp(s - m_new)
        l = alpha * l + p.sum(axis=-1, keepdims=True)
        v_copy.wait()

        @pl.when(left < block)
        def _():
          row = jax.lax.broadcasted_iota(jnp.int32, (block, c), 0)
          v_buf[buf] = jnp.where(row < left, v_buf[buf],
                                 0).astype(v_buf.dtype)

        # p as three bf16 terms that sum to it: three exact passes, f32 sums
        terms, rest = [], p
        for _ in range(3):
          terms.append(rest.astype(jnp.bfloat16))
          rest = rest - terms[-1].astype(jnp.float32)
        pv = jnp.dot(jnp.concatenate(terms, axis=0), v_buf[buf],
                     preferred_element_type=jnp.float32)   # [3 hp, c]
        acc[...] = alpha * acc[...] + (pv[:hp] + pv[hp:2 * hp] + pv[2 * hp:])
        return m_new, l

      _, total = jax.lax.fori_loop(0, blocks, one_block, (m_own, l_own))
      o_ref[i] = fold(acc[...] / total)

    return first + blocks

  jax.lax.fori_loop(0, slots, one_slot, 0)


# jitted under the name a reader of a device trace should see (the rule
# ops/layer_norm.py's launchers state): the innermost jit names the kernel
@functools.partial(jax.jit, static_argnames=("interpret", "scale"))
def decode_attention(q, k, v, cached_k, cached_v, lengths, skip=None,
                     sink=None, interpret=False, scale=None, keep=None):
  """Softmax attention of one query token a slot over that slot's cache
  rows below ``lengths[i]`` AND the token's own key and value: ``q [b, h,
  d]`` (rotated), ``k`` / ``v`` ``[b, kv_heads, d]`` as the cache will hold
  them, ``cached_k`` / ``cached_v`` ``[b, max, kv_heads * d]`` as they were
  before the step's write, ``lengths [b]`` int32 (clamped into ``[0,
  max]``). Query head ``i`` reads KV head ``i // g``. ``skip [2, b]`` int32
  (a ring leaf): for each slot the first row and the count of a cyclic run
  of rows that is not attended. ``v`` / ``cached_v`` may be ``dv`` wide a
  head where the keys are ``d``; ``sink [h]`` float32 joins each head's
  softmax denominator. ``scale`` (static) is the scores' scale, None =
  ``d^-0.5``. ``keep`` is a selection's ``(rows [b, max] bool, own [b]
  bool)``: slot ``i`` attends cache row ``r`` only where ``rows[i, r]``, and
  its own token only where ``own[i]`` (a selection is not met with a sink or a
  ring). Returns ``[b, h, dv]`` float32. The shapes must pass
  :func:`supports`."""
  if keep is not None and (skip is not None or sink is not None):
    raise ValueError("decode_attention takes a selection's keep mask over a "
                     "whole-context leaf under a plain softmax: not beside a "
                     "ring's skip or a sink")
  if not supports(q.shape, q.dtype, cached_k.shape, cached_k.dtype,
                  cached_v.shape, keep is not None):
    raise ValueError(
        "decode_attention takes bf16 queries [b, h, d] over bf16 leaves "
        "[b, max, kv_heads * d] of whole lanes and whole blocks of %d rows, "
        "got %s %s over %s %s and %s" % (BLOCK, q.dtype, q.shape,
                                         cached_k.dtype, cached_k.shape,
                                         cached_v.shape))
  b, h, dk = q.shape
  c, cv = cached_k.shape[2], cached_v.shape[2]
  hk = c // dk
  d = cv // hk                 # a VALUE head's lanes: what the output folds on
  g, hp, w = h // hk, _padded_heads(h), max(d, LANES)
  # head i's d values in KV head i // g's lanes, zeros elsewhere
  own = jnp.repeat(jnp.eye(hk, dtype=q.dtype), g, axis=0)[None, :, :, None]
  q_bd = jnp.pad((q[:, :, None, :] * own).reshape(b, h, c),
                 ((0, 0), (0, hp - h), (0, 0)))
  hbm = pl.BlockSpec(memory_space=pltpu.HBM)
  vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
  ring, sunk, kept = skip is not None, sink is not None, keep is not None
  scalars = (lengths.astype(jnp.int32),) + (
      (skip.astype(jnp.int32).reshape(2 * b),) if ring else ()) + (
          (keep[1].astype(jnp.int32).reshape(b),) if kept else ())
  masks = (keep[0].astype(jnp.int32).reshape(b, -1, BLOCK),) if kept else ()
  sinks = (jnp.broadcast_to(jnp.pad(
      sink.astype(jnp.float32), (0, hp - h))[:, None], (hp, LANES)),) \
      if sunk else ()
  o = pl.pallas_call(
      functools.partial(
          _kernel, g=g, d=d, ring=ring, sunk=sunk, kept=kept,
          scale=1.0 / (dk ** 0.5) if scale is None else scale),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          # ONE grid step, the slots a loop inside it (a grid over slots
          # with the chain's state in SMEM took the same time on the chip)
          num_scalar_prefetch=len(scalars), grid=(1,),
          in_specs=[vmem] * (len(sinks) + len(masks))
          + [vmem, vmem, vmem, hbm, hbm],
          out_specs=vmem,
          scratch_shapes=[pltpu.VMEM((2, BLOCK, c), cached_k.dtype),
                          pltpu.VMEM((2, BLOCK, cv), cached_v.dtype),
                          pltpu.VMEM((hp, cv), jnp.float32),
                          pltpu.SemaphoreType.DMA((2, 2))]),
      out_shape=jax.ShapeDtypeStruct((b, hp, w), jnp.float32),
      compiler_params=pltpu.CompilerParams(
          vmem_limit_bytes=VMEM_BUDGET + (8 << 20)),
      interpret=interpret,
      name="decode_attention",
  )(*scalars, *sinks, *masks, q_bd, k.reshape(b, 1, c).astype(q.dtype),
    v.reshape(b, 1, cv).astype(q.dtype), cached_k, cached_v)
  o = o[:, :h]
  if w == d:
    return o
  # a head narrower than a vreg sits in its KV head's part of the 128 lanes
  part = jax.nn.one_hot((jnp.arange(h) // g) % (w // d), w // d,
                        dtype=o.dtype)
  return (o.reshape(b, h, w // d, d) * part[None, :, :, None]).sum(axis=2)
