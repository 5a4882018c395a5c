"""The exact top-``k`` of each row as a mask, the threshold search in VMEM.

``scores [rows, n]`` float32 are a learned indexer's scores of ``rows`` queries
over ``n`` cached positions, ``last [rows]`` each query's last candidate
(candidates are columns ``0..last``): the result is true at the ``min(k,
candidates)`` candidates with the largest score, the EARLIER position first
among equal scores (``lax.top_k``'s order), ``-0.0`` as ``0.0``; a row without a
candidate keeps none. It is ``models.transformer.select_topk``'s mask bit for
bit, and that function (the same search as XLA operations, which keeps every
input this kernel does not take) is its reference in the tests.

The search itself is the reference's: a float's bits, with the sign folded,
order as the floats do, and a row's ``k``-th largest key is the largest
threshold ``T`` with ``count(key >= T) >= k``, found bit by bit from the top in
32 passes of compare-and-count. As XLA operations each pass reads the row from
HBM: a 4096-token chunk's ``[4096, 32768]`` scores are 537 MB, read 32 times
(in halves) whatever the cursor, 30.3 ms a layer a chunk where one read takes
0.66 (PERF.md section 6, PR 45). Here the grid runs over tiles of up to 64
rows (``ROWS``); a tile's scores come into VMEM ONCE, only the blocks of ``block``
columns up to the tile's largest ``last`` (a prefetched scalar a tile, the
next tile's blocks on their way while this one is searched), are folded into
keys there, and every pass is a loop over those resident blocks. Keys above
the threshold stay; of the keys EQUAL to it the first ``k - count(key > T)``
by position, by a second search of the same kind over the position (only in
a tile where some row has more equals than it needs: rare with real scores).
The mask leaves as int8, one write.

The keys are held as SIGNED integers (the unsigned key with its top bit
flipped: the same order under a signed compare, which is the vector unit's
own); a column that is nobody's candidate holds the least of them, which no
threshold reaches.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: lanes of a vreg; ``n`` is whole lanes
LANES = 128
#: rows of a tile, the smallest of these that holds every row, else the
#: largest: a pass closes with a lane reduction that the next threshold waits
#: for, so more rows a tile hide more of it, and rows that are padding still
#: cost their passes. On the chip, 2048 of 32768 (PERF.md section 6, PR 45),
#: a chunk's 4096 rows ending at 16384 | 32768 and a step's 16 rows, ms a
#: call: 8 rows 8.14 | 13.47 | 0.044, 16 rows 4.48 | 7.36 | 0.029, 32 rows
#: 3.18 | 5.45 | 0.045, 64 rows 2.57 | 4.62 | 0.070, 128 rows 2.31 | 4.44 |
#: 0.122 (58 MB of VMEM)
ROWS = (16, 32, 64)
#: columns of a block, the widest of these that divides ``n``: what one DMA
#: brings a row tile of, and the grain of "up to the tile's last candidate"
#: (the same calls at 32 rows: 512 columns 3.71 | 6.62, 1024 3.18 | 5.45,
#: 2048 2.95 | 4.91; at 64 rows 1024 2.57 | 4.62, 2048 2.48 | 4.37)
BLOCKS = (2048, 1024, 512, 256, 128)
#: what the kernel may hold in VMEM (:func:`_vmem_bytes`; 64 rows of 32768:
#: 29 MB)
VMEM_BUDGET = 64 << 20

_LOWEST = -(1 << 31)        # the signed key of the unsigned key 0


def _block(n: int) -> int:
  return next(b for b in BLOCKS if n % b == 0)


def _tile(m: int) -> int:
  return next((r for r in ROWS if m <= r), ROWS[-1])


def _vmem_bytes(m: int, n: int) -> int:
  """A tile's scores as they land (float32, two tiles: this one and the
  next), its keys (int32) and its mask (int8, double-buffered)."""
  return _tile(m) * n * (2 * 4 + 4 + 2 * 1)


def supports(shape, dtype, mesh=None) -> bool:
  """Whether :func:`select_topk` can take ``scores`` of ``shape [..., n]``:
  float32, at least one row, ``n`` whole lanes, one device (the kernel is not
  partitioned over a mesh) and a tile of rows in VMEM."""
  if len(shape) < 2 or (mesh is not None and mesh.size > 1):
    return False
  m, n = math.prod(shape[:-1]), shape[-1]
  return (jnp.dtype(dtype) == jnp.float32 and m > 0 and n > 0
          and n % LANES == 0 and _vmem_bytes(m, n) <= VMEM_BUDGET)


def _kernel(live_ref, last_ref, scores_hbm, out_ref, land, keys, sem, *, k,
            block):
  i, tiles = pl.program_id(0), pl.num_programs(0)
  rows, n = keys.shape
  slot = i % 2
  lane = lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)

  def cols(j):
    return pl.ds(pl.multiple_of(j * block, block), block)

  def pieces(j):
    """Block j a ``[rows, LANES]`` piece at a time: (columns, positions)."""
    for c in range(block // LANES):
      first = j * block + c * LANES
      yield pl.ds(pl.multiple_of(first, LANES), LANES), lane + first

  def copy(tile, j, into):
    return pltpu.make_async_copy(
        scores_hbm.at[pl.ds(pl.multiple_of(tile * rows, rows), rows),
                      cols(j)],
        land.at[into, :, cols(j)], sem.at[into])

  def start(tile, into):
    def one(j, _):
      copy(tile, j, into).start()
      return _
    lax.fori_loop(0, live_ref[tile], one, 0)

  @pl.when(i == 0)
  def _():
    start(0, 0)

  @pl.when(i + 1 < tiles)
  def _():
    start(i + 1, 1 - slot)

  live = live_ref[i]                       # the blocks that hold a candidate
  last = last_ref[...]                     # [rows, 1]
  want = jnp.minimum(jnp.clip(last + 1, 0, n), k)

  def fold(j, _):
    """Block j's scores, as they arrive, into ordered keys."""
    copy(i, j, slot).wait()
    for piece, at in pieces(j):
      s = land[slot, :, piece]
      bits = lax.bitcast_convert_type(jnp.where(s == 0, 0.0, s), jnp.int32)
      key = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
      keys[:, piece] = jnp.where(at <= last, key, _LOWEST)
    return _

  lax.fori_loop(0, live, fold, 0)

  def count(test):
    """A row's entries over the live blocks that ``test(keys, positions)``
    marks, a ``[rows, LANES]`` piece at a time."""
    def one(j, acc):
      for piece, at in pieces(j):
        acc = acc + jnp.where(test(keys[:, piece], at), 1, 0)
      return acc
    acc = lax.fori_loop(0, live, one, jnp.zeros((rows, LANES), jnp.int32))
    return jnp.sum(acc, axis=1, keepdims=True)

  def wide(x):
    return jnp.broadcast_to(x, (rows, LANES))

  def key_bit(b, carry):
    """``t``: the unsigned threshold's bits so far, as an int32; ``got``: the
    keys at or above it."""
    t, got = carry
    cand = t | (jnp.int32(1) << (31 - b))
    edge = wide(cand ^ _LOWEST)
    reach = count(lambda x, at: x >= edge)
    enough = reach >= want
    return jnp.where(enough, cand, t), jnp.where(enough, reach, got)

  t, got = lax.fori_loop(0, 32, key_bit, (
      jnp.zeros((rows, 1), jnp.int32), jnp.full((rows, 1), n + 1, jnp.int32)))
  t = jnp.where(t == 0, 1, t)              # a row with no candidate keeps none
  edge = wide(t ^ _LOWEST)

  # more keys at or above the threshold than the row keeps: of the keys EQUAL
  # to it, those at positions below ``stop``, the largest with count(equal,
  # position < stop) <= need
  bits = n.bit_length()

  def first_equals():
    need = want - count(lambda x, at: x > edge)

    def position_bit(b, stop):
      cand = stop | (jnp.int32(1) << (bits - 1 - b))
      upto = wide(cand)
      below = count(lambda x, at: jnp.logical_and(x == edge, at < upto))
      return jnp.where(below <= need, cand, stop)
    return lax.fori_loop(0, bits, position_bit,
                         jnp.zeros((rows, 1), jnp.int32))

  tied = jnp.max(jnp.where(got > want, 1, 0)) > 0
  stop = wide(lax.cond(tied, first_equals,
                       lambda: jnp.full((rows, 1), n, jnp.int32)))

  def keep(j, _):
    for piece, at in pieces(j):
      x = keys[:, piece]
      stays = jnp.logical_or(
          x > edge, jnp.logical_and(x == edge, at < stop))
      out_ref[:, piece] = jnp.where(stays, 1, 0).astype(jnp.int8)
    return _

  lax.fori_loop(0, live, keep, 0)

  def none(j, _):
    out_ref[:, cols(j)] = jnp.zeros((rows, block), jnp.int8)
    return _

  lax.fori_loop(live, n // block, none, 0)


# jitted under the name a reader of a device trace should see (the rule
# ops/layer_norm.py's launchers state): the innermost jit names the kernel
@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def select_topk(scores, last, k: int, interpret=False):
  """The exact top-``k`` of each row among its candidates: ``scores [..., n]``
  float32, ``last [...]`` integers (a row's candidates are columns ``0..last``;
  under 0 it has none, from ``n - 1`` on all of them). Returns ``[..., n]``
  bool, true at the ``min(k, candidates)`` candidates with the largest score,
  the earlier position first among equal scores, ``-0.0`` as ``0.0``. The
  shapes must pass :func:`supports`."""
  if not supports(scores.shape, scores.dtype) \
      or last.shape != scores.shape[:-1]:
    raise ValueError(
        "select_topk takes float32 scores [..., n] with n a multiple of %d "
        "and one last candidate a row, got %s %s and %s"
        % (LANES, scores.dtype, scores.shape, last.shape))
  n = scores.shape[-1]
  block = _block(n)
  flat = scores.reshape(-1, n)
  m = flat.shape[0]
  rows = _tile(m)
  mp = -(-m // rows) * rows
  at = jnp.clip(last.reshape(-1).astype(jnp.int32), -1, n - 1)
  if mp != m:
    flat = jnp.pad(flat, ((0, mp - m), (0, 0)))
    at = jnp.pad(at, (0, mp - m), constant_values=-1)
  live = (jnp.max(at.reshape(-1, rows), axis=1) + block) // block
  out = pl.pallas_call(
      functools.partial(_kernel, k=k, block=block),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=1, grid=(mp // rows,),
          in_specs=[pl.BlockSpec((rows, 1), lambda i, live: (i, 0)),
                    pl.BlockSpec(memory_space=pl.ANY)],
          out_specs=pl.BlockSpec((rows, n), lambda i, live: (i, 0)),
          scratch_shapes=[pltpu.VMEM((2, rows, n), jnp.float32),
                          pltpu.VMEM((rows, n), jnp.int32),
                          pltpu.SemaphoreType.DMA((2,))]),
      out_shape=jax.ShapeDtypeStruct((mp, n), jnp.int8),
      compiler_params=pltpu.CompilerParams(
          # a tile starts the next one's DMAs: the tiles run in order
          dimension_semantics=("arbitrary",),
          vmem_limit_bytes=VMEM_BUDGET + (8 << 20)),
      interpret=interpret,
      name="select_topk",
  )(live, at[:, None], flat)
  return (out[:m] != 0).reshape(scores.shape)
