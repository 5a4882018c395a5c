"""One new row a slot into a cache leaf, in place: the serving decode step's
per-slot cursor write as a DMA kernel.

``buf [b, max, c]`` is one leaf of the KV slab (K or V of a layer, an MLA
latent cache), ``idx [b]`` each slot's cursor, ``val [b, c]`` the step's new
row a slot. XLA lowers the same write (a ``vmap`` of ``dynamic_update_slice``,
what ``models.transformer._cache_write`` keeps for every input this kernel
does not take) to a loop of ``b`` bounds-checked update-slices a leaf, each
iteration several tiny device operations: a third of a GPT-2 decode step's
device time to move 2.9 MB (PERF.md section 6, PR 29).

The kernel never sees the leaf in fast memory. The leaf stays where it lies
in HBM, aliased onto the output; for each slot the ALIGNED tile of rows that
holds the cursor's row (a DMA cannot address one row of a packed dtype: a
bf16 row shares its 32-bit words with its neighbour) is read into VMEM,
patched there, and written back. Slots never share a tile (the slot axis is
major), so the ``b`` reads are all in flight together, and the writes too.

Semantics are ``dynamic_update_slice``'s, to the bit: a negative start counts
from the end, and the start is then CLAMPED into ``[0, max - 1]`` (a frozen
lane at cursor == ``max`` rewrites its own last row, never the next slot's
first).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: lanes of a vreg; a leaf takes the kernel only if its minor axis fills them
LANES = 128


def tile_rows(dtype) -> int:
  """Rows of one packed sublane tile: 8 of float32, 16 of bfloat16, 32 of
  int8 — the fewest rows of that dtype a DMA can address in a tiled leaf."""
  return 32 // jnp.dtype(dtype).itemsize


#: what the kernel may hold in VMEM: the tiles and the (tile-padded, double
#: buffered) new rows are 3 x slots x one tile row of 32 bytes x c. A slab of
#: more slots than fit keeps the loop (16 x 1280: 2 MB; 48 x 640: 3 MB)
VMEM_BUDGET = 8 << 20


def supports(shape, dtype) -> bool:
  """Whether :func:`cursor_write` can take a leaf of this shape: three
  axes, the minor one lane-dense, the position axis whole tiles, and every
  slot's tile in VMEM at once."""
  return (len(shape) == 3 and shape[2] % LANES == 0
          and jnp.dtype(dtype).itemsize in (1, 2, 4)
          and shape[1] % tile_rows(dtype) == 0
          and 3 * shape[0] * 32 * shape[2] <= VMEM_BUDGET)


def _kernel(idx_ref, val_ref, buf_ref, out_ref, tile, sem, *, rows):
  del buf_ref                      # aliased onto out_ref: one buffer in HBM
  b, mx, _ = out_ref.shape
  row = jax.lax.broadcasted_iota(jnp.int32, tile.shape[1:], 0)

  def window(i):
    """(first row of slot i's tile, the cursor's row within it)."""
    p = idx_ref[i]
    p = jnp.clip(jnp.where(p < 0, p + mx, p), 0, mx - 1)
    base = pl.multiple_of((p // rows) * rows, rows)
    return base, p - base

  def copy(i, back: bool):
    hbm = out_ref.at[i, pl.ds(window(i)[0], rows)]
    src, dst = (tile.at[i], hbm) if back else (hbm, tile.at[i])
    return pltpu.make_async_copy(src, dst, sem.at[i])

  def each(fn):
    jax.lax.fori_loop(0, b, lambda i, _: fn(i), None)

  each(lambda i: copy(i, False).start())     # every read in flight at once

  def patch(i):
    copy(i, False).wait()
    tile[i] = jnp.where(row == window(i)[1], val_ref[i], tile[i])
    copy(i, True).start()
  each(patch)
  each(lambda i: copy(i, True).wait())


# jitted under the name a reader of a device trace should see (the rule
# ops/layer_norm.py's launchers state): the innermost jit names the kernel
@functools.partial(jax.jit, static_argnames=("interpret",))
def cursor_write(buf, val, idx, interpret=False):
  """``buf [b, max, c]`` with row ``idx[i]`` of slot ``i`` (clamped as
  ``dynamic_update_slice`` clamps it) replaced by ``val[i]`` (``val [b, c]``,
  ``idx [b]`` int32); every other row is the input's, and inside a program
  that donates ``buf`` the result IS its buffer. The shape must pass
  :func:`supports`."""
  if not supports(buf.shape, buf.dtype):
    raise ValueError("cursor_write takes [b, max, c] leaves with c a "
                     "multiple of %d, max of %d rows and b x c x 96 bytes "
                     "of VMEM under %d, got %s %s"
                     % (LANES, tile_rows(buf.dtype), VMEM_BUDGET, buf.dtype,
                        buf.shape))
  b, _, c = buf.shape
  rows = tile_rows(buf.dtype)
  hbm = pl.BlockSpec(memory_space=pltpu.HBM)
  return pl.pallas_call(
      functools.partial(_kernel, rows=rows),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=1, grid=(1,),
          # val as [b, 1, c]: a slot is then an index on the MAJOR axis (a
          # dynamic index into packed sublanes does not lower)
          in_specs=[pl.BlockSpec((b, 1, c), lambda i, idx: (0, 0, 0)), hbm],
          out_specs=hbm,
          scratch_shapes=[pltpu.VMEM((b, rows, c), buf.dtype),
                          pltpu.SemaphoreType.DMA((b,))]),
      # an HBM-typed result pins the aliased operand to HBM as well: with a
      # plain out_shape the compiler ran the kernel ON the copy of the leaf
      # it had staged in fast memory for the attention's contraction and
      # copied 68 of 72 leaves back whole (PERF.md section 6, PR 29)
      out_shape=pltpu.HBM(buf.shape, buf.dtype),
      input_output_aliases={2: 0},
      interpret=interpret,
      name="cursor_write",
  )(idx.astype(jnp.int32), val.astype(buf.dtype).reshape(b, 1, c), buf)
