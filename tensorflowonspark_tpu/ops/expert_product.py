"""The grouped product of sparse experts, ``lhs [M, K] x rhs [G, K, N]`` by
``group_sizes [G]``, as one kernel that reads the rows that HAVE a group and
the matrices that have rows, and nothing else.

``lhs`` holds one row an assignment of a token to an expert, sorted by expert:
the first ``group_sizes[0]`` rows meet ``rhs[0]``, the next ``group_sizes[1]``
rows ``rhs[1]``, and so on; the rows behind the last group (assignments to
experts held on another chip) meet nothing and read ZERO in the result. The
same product by XLA (``lax.ragged_dot``, what
``parallel.expert_parallel.held_experts_ffn`` keeps for every input this
kernel does not take) costs each TOUCHED group a fixed time whatever its rows,
65 us for a 19 MB matrix that streams in 23: 2.07 ms a product in a 2048-token
chunk of the Trinity cell (8192 rows of which 983 have a group, 32 matrices =
0.74 ms of bytes; handing it the held rows alone reads 2.05), 0.27 ms in a
decode step that touches 9 of the 32. This kernel reads 0.96 and 0.25 (PERF.md
section 5, PR 41).

The grid walks the (row tile, group) pairs that intersect, in order: a tile of
``ROWS`` rows meets each group that has a row in it, a group each tile it has
a row in. The pairs are counted and listed OUTSIDE the kernel from the group
sizes (a handful of tiny fused operations, the same for the three products of
a layer) and handed in as prefetched scalars; their number is the grid's
length, so a tile behind the last group is never visited and an expert nobody
chose costs no DMA. A pair multiplies the whole tile by the group's matrix
(``[rows, K] x [K, tn]``, bf16 x bf16 accumulated in f32 over the whole of
``K`` in one contraction) and stores the rows that are the group's. The
columns are the OUTER axis of the grid: consecutive pairs of one group then
ask for the block they already hold, so each touched matrix is streamed once
whatever the number of tiles its rows span; the price is the live rows of
``lhs`` read once a column block, a few percent of the matrices' bytes.

Float32 activations go in as bf16 TERMS that sum to them (``split`` of
``held_experts_ffn``): the same kernel, n times the rows.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: lanes of a vreg; K and N are whole lanes
LANES = 128
#: rows of a tile: the MXU's own 128. A pair costs the load of the matrix's
#: weights whatever its rows up to here, so a smaller tile only adds pairs and
#: a larger one streams rows that are another group's. On the chip, a product
#: of a Trinity chunk (30 rows a group): 64 rows 0.995 ms, 128 0.959, 256
#: 0.985; at 64 rows a group (MiMo, DeepSeek) 256 reads 2-4% under 128, in a
#: decode step 4-10% over it (PERF.md section 5, PR 41)
ROWS = 128
#: rows of a packed bf16 sublane tile: the row axis is padded to whole ones
SUBLANES = 16
#: bytes of one ``[K, tn]`` block of a matrix (it is double-buffered): blocks
#: this large hide the grid's step behind their DMA, and the first one's
#: unhidden DMA stays a few percent of a call. On the chip, the same product:
#: 2 MB 1.02 ms, 4 MB 0.97, 8 MB 0.96, 16 MB 0.95; DeepSeek's (K of 7168, the
#: live rows read once a column block) 1.03, 0.93, 0.87, 0.86
BLOCK_BYTES = 8 << 20
#: what the kernel may hold in VMEM (:func:`_vmem_bytes`)
VMEM_BUDGET = 48 << 20


def _padded(m: int) -> int:
  """``m`` rows as the kernel holds them: whole packed bf16 sublane tiles."""
  return -(-m // SUBLANES) * SUBLANES


def _tiles(m: int, k: int, n: int):
  """``(tm, tn)`` from the static shape: the row tile (all rows where there
  are fewer than :data:`ROWS`) and the widest whole-lane divisor of ``n`` whose
  ``[k, tn]`` block stays inside :data:`BLOCK_BYTES`."""
  tn = max((t for t in range(LANES, n + 1, LANES)
            if n % t == 0 and k * t * 2 <= BLOCK_BYTES), default=LANES)
  return min(ROWS, _padded(m)), tn


def _vmem_bytes(m: int, k: int, n: int) -> int:
  """The row tile (bf16), the matrix block (bf16) and the result block (f32),
  each double-buffered, and the product before its masked store."""
  tm, tn = _tiles(m, k, n)
  return 2 * (tm * k * 2 + k * tn * 2 + tm * tn * 4) + tm * tn * 4


def supports(lhs_shape, lhs_dtype, rhs_shape, rhs_dtype) -> bool:
  """Whether :func:`expert_product` can take ``lhs [M, K]`` by ``rhs [G, K,
  N]``: both bf16 (a float32 stack keeps ``ragged_dot`` at
  ``Precision.HIGHEST``), ``K`` and ``N`` whole lanes, at least one row, and
  the blocks in VMEM."""
  if len(lhs_shape) != 2 or len(rhs_shape) != 3:
    return False
  m, k = lhs_shape
  _, k2, n = rhs_shape
  return (jnp.dtype(lhs_dtype) == jnp.bfloat16
          and jnp.dtype(rhs_dtype) == jnp.bfloat16
          and k == k2 and m > 0 and rhs_shape[0] > 0
          and k % LANES == 0 and n % LANES == 0
          and _vmem_bytes(m, k, n) <= VMEM_BUDGET)


def pairs(sizes, m: int, tm: int):
  """The (row tile, group) pairs a product visits, from ``sizes [G]``:
  ``(starts [G + 1], groups [P], tiles [P], count)`` with ``P = cdiv(m, tm) +
  G - 1`` the most there can be. Pair ``p < count`` is tile ``tiles[p]``
  against group ``groups[p]``, whose rows are ``[starts[g], starts[g + 1])``;
  pairs are in the order of the groups, a group's in the order of its tiles.
  ``count`` is at least 1: with no row in any group pair 0 is tile 0 against
  the last group, which stores nothing but the tile's zeros."""
  g = sizes.shape[0]
  most = -(-m // tm) + g - 1
  # cumulative sums as one masked reduction each: a fusion, not a scan
  upto = jnp.arange(g)[:, None] >= jnp.arange(g)[None, :]
  ends = jnp.sum(jnp.where(upto, sizes[None, :], 0), axis=1)
  starts = ends - sizes
  first = starts // tm
  spans = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
  after = jnp.sum(jnp.where(upto, spans[None, :], 0), axis=1)  # pairs so far
  p = jnp.arange(most)
  groups = jnp.minimum(
      jnp.sum(after[None, :] <= p[:, None], axis=1), g - 1).astype(jnp.int32)
  tiles = first[groups] + p - (after - spans)[groups]
  tiles = jnp.clip(tiles, 0, -(-m // tm) - 1).astype(jnp.int32)
  return (jnp.concatenate([starts, ends[-1:]]).astype(jnp.int32), groups,
          tiles, jnp.maximum(after[-1], 1).astype(jnp.int32))


def _kernel(starts_ref, groups_ref, tiles_ref, lhs_ref, rhs_ref, out_ref, *,
            tm):
  p = pl.program_id(1)
  g, tile = groups_ref[p], tiles_ref[p]
  acc = jnp.dot(lhs_ref[...], rhs_ref[...],
                preferred_element_type=jnp.float32)
  row = tile * tm + lax.broadcasted_iota(jnp.int32, acc.shape, 0)
  own = jnp.logical_and(row >= starts_ref[g], row < starts_ref[g + 1])
  # the tile's first pair writes every row (zeros where the rows are another
  # group's or nobody's), a later pair only its own
  opens = jnp.logical_or(p == 0, tiles_ref[jnp.maximum(p - 1, 0)] != tile)

  @pl.when(opens)
  def _():
    out_ref[...] = jnp.where(own, acc, 0.0)

  @pl.when(jnp.logical_not(opens))
  def _():
    out_ref[...] = jnp.where(own, acc, out_ref[...])


def _call(lhs, rhs, sizes, interpret):
  m, k = lhs.shape
  n = rhs.shape[2]
  tm, tn = _tiles(m, k, n)
  mp = _padded(m)
  if mp != m:
    lhs = jnp.pad(lhs, ((0, mp - m), (0, 0)))
  starts, groups, tiles, count = pairs(sizes.astype(jnp.int32), mp, tm)
  out = pl.pallas_call(
      functools.partial(_kernel, tm=tm),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=3, grid=(n // tn, count),
          in_specs=[
              pl.BlockSpec((tm, k), lambda j, p, s, g, t: (t[p], 0)),
              pl.BlockSpec((None, k, tn), lambda j, p, s, g, t: (g[p], 0, j)),
          ],
          out_specs=pl.BlockSpec((tm, tn), lambda j, p, s, g, t: (t[p], j))),
      out_shape=jax.ShapeDtypeStruct((mp, n), jnp.float32),
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("arbitrary", "arbitrary"),
          vmem_limit_bytes=VMEM_BUDGET + (8 << 20)),
      interpret=interpret,
      name="expert_product",
  )(starts, groups, tiles, lhs, rhs)
  # a tile behind the last group was never visited: whatever lies there is
  # not the result (the consumer's fusion takes this select in)
  live = jnp.arange(mp)[:, None] < starts[-1]
  return jnp.where(live, out, 0.0)[:m]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _product(lhs, rhs, sizes, interpret):
  return _call(lhs, rhs, sizes, interpret)


def _product_fwd(lhs, rhs, sizes, interpret):
  return _call(lhs, rhs, sizes, interpret), (lhs, rhs, sizes)


def _product_bwd(interpret, saved, ct):
  """The backward of ``lax.ragged_dot`` at the same operands: the kernel is
  the forward only."""
  del interpret
  lhs, rhs, sizes = saved
  _, vjp = jax.vjp(lambda a, b: lax.ragged_dot(
      a, b, sizes, preferred_element_type=jnp.float32), lhs, rhs)
  return vjp(ct) + (np.zeros(sizes.shape, jax.dtypes.float0),)


_product.defvjp(_product_fwd, _product_bwd)


# jitted under the name a reader of a device trace should see (the rule
# ops/layer_norm.py's launchers state): the innermost jit names the kernel
@functools.partial(jax.jit, static_argnames=("interpret",))
def expert_product(lhs, rhs, group_sizes, interpret=False):
  """``out[r] = lhs[r] @ rhs[g]`` for the rows ``r`` of group ``g`` (the
  first ``group_sizes[0]`` rows are group 0's, and so on), ZERO for the rows
  behind the last group: ``lhs [M, K]`` and ``rhs [G, K, N]`` bf16,
  ``group_sizes [G]`` integers that sum to at most ``M``. Returns ``[M, N]``
  float32 (bf16 products accumulated in f32, ``lax.ragged_dot``'s
  ``preferred_element_type=float32``); differentiable, with ``ragged_dot``'s
  backward. The shapes must pass :func:`supports`."""
  if not supports(lhs.shape, lhs.dtype, rhs.shape, rhs.dtype):
    raise ValueError(
        "expert_product takes bf16 rows [M, K] by bf16 matrices [G, K, N] "
        "with K and N multiples of %d, got %s %s by %s %s"
        % (LANES, lhs.dtype, lhs.shape, rhs.dtype, rhs.shape))
  return _product(lhs, rhs, group_sizes, interpret)
