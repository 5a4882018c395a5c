"""``ops.cursor_write`` (the serving decode step's per-slot cache write as a
DMA kernel) against the write it replaces: a ``vmap`` of
``lax.dynamic_update_slice``, bit for bit, in interpret mode on the CPU.
What interpret mode cannot show (the Mosaic lowering, the leaf staying in
HBM and in place) is ``tests/test_mosaic_gate.py``'s; what only the chip
shows (the time, the served tokens) is PERF.md section 6, PR 29.
"""

from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu import ops
from tensorflowonspark_tpu.models import transformer as tfm

MAX = 64            # whole tiles of every dtype (8 / 16 / 32 rows)


def _loop(buf, val, idx):
  """The lowering ``_cache_write`` keeps where the kernel does not apply."""
  return jax.vmap(lambda row, v, i: jax.lax.dynamic_update_slice(
      row, v[None], (i, 0)))(buf, val, idx)


def _draw(key, shape, dtype):
  if dtype == jnp.int8:
    return jax.random.randint(key, shape, -127, 128).astype(jnp.int8)
  return jax.random.normal(key, shape).astype(dtype)


# cursors a serving slab really holds: 0 (a fresh slot), odd and even rows
# (the two halves of a packed bf16 word), a tile's last and first row, max -
# 1, and max: a lane frozen at max_total, which must CLAMP onto its own last
# row and never reach the next slot's first
CURSORS = (0, 1, 2, 15, 16, 31, 33, MAX - 1, MAX)


@pytest.mark.parametrize("c", [128, 640, 1280])
@pytest.mark.parametrize("b", [1, 16, 48])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32, jnp.int8],
                         ids=["bf16", "f32", "int8"])
def test_equals_the_vmapped_update_slice_bit_for_bit(dtype, b, c):
  kb, kv = jax.random.split(jax.random.PRNGKey(b * 7 + c))
  buf, val = _draw(kb, (b, MAX, c), dtype), _draw(kv, (b, c), dtype)
  # one call a cursor for a single slot; else the cursors spread over the
  # slots, the clamped one LAST but one (its neighbour must stay whole)
  cursor_sets = [[p] for p in CURSORS] if b == 1 else \
      [[CURSORS[(i + 2) % len(CURSORS)] for i in range(b)]]
  for cursors in cursor_sets:
    idx = jnp.asarray(cursors, jnp.int32)
    out = ops.cursor_write(buf, val, idx, interpret=True)
    assert out.dtype == buf.dtype and out.shape == buf.shape
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(_loop(buf, val, idx),
                                             np.float32))
    # and said directly: the written row holds val, every other row of
    # every slot is the input's
    at = np.minimum(np.asarray(cursors), MAX - 1)
    got, was = np.asarray(out, np.float32), np.array(buf, np.float32)
    np.testing.assert_array_equal(got[np.arange(b), at],
                                  np.asarray(val, np.float32))
    was[np.arange(b), at] = got[np.arange(b), at]
    np.testing.assert_array_equal(got, was)


def test_a_negative_cursor_counts_from_the_end_as_update_slice_does():
  buf = _draw(jax.random.PRNGKey(0), (2, MAX, 128), jnp.bfloat16)
  val = _draw(jax.random.PRNGKey(1), (2, 128), jnp.bfloat16)
  idx = jnp.asarray([-3, -MAX - 9], jnp.int32)
  np.testing.assert_array_equal(
      np.asarray(ops.cursor_write(buf, val, idx, interpret=True), np.float32),
      np.asarray(_loop(buf, val, idx), np.float32))


@pytest.mark.parametrize("shape,dtype,why", [
    ((16, MAX, 20), jnp.float32, "an int8 cache's scale leaf: 20 lanes"),
    ((16, MAX, 192), jnp.bfloat16, "a minor axis off the lane tiling"),
    ((16, 40, 128), jnp.bfloat16, "40 rows are not whole bf16 tiles"),
    ((16, 24, 128), jnp.int8, "24 rows are not whole int8 tiles"),
    ((16, MAX, 2, 64), jnp.bfloat16, "a 4-D leaf"),
    ((128, MAX, 1280), jnp.bfloat16, "128 slots' tiles are 15 MB of VMEM"),
])
def test_shapes_the_kernel_does_not_take(shape, dtype, why):
  assert not ops.cursor_write_supports(shape, dtype), why
  if len(shape) == 3:
    with pytest.raises(ValueError, match="cursor_write takes"):
      ops.cursor_write(jnp.zeros(shape, dtype),
                       jnp.zeros((shape[0], shape[2]), dtype),
                       jnp.zeros((shape[0],), jnp.int32), interpret=True)


@pytest.mark.parametrize("case,kernels,mesh,shape,dtype,dma", [
    ("the serving decode step on a chip", True, None, (4, MAX, 128),
     jnp.bfloat16, True),
    ("a one-device mesh", True, SimpleNamespace(size=1), (4, MAX, 128),
     jnp.bfloat16, True),
    ("a tensor mesh: GSPMD cannot partition the call", True,
     SimpleNamespace(size=4), (4, MAX, 128), jnp.bfloat16, False),
    ("the CPU: no Pallas kernels under auto", False, None, (4, MAX, 128),
     jnp.bfloat16, False),
    ("an int8 cache's scales", True, None, (4, MAX, 20), jnp.float32, False),
])
def test_cache_write_picks_the_lowering_from_what_it_observes(
    monkeypatch, case, kernels, mesh, shape, dtype, dma):
  """``_cache_write``'s per-slot single-token branch: the kernel for a
  lane-dense leaf of whole tiles on one device where Pallas kernels are
  on, the loop elsewhere; the same array either way, and the tally says
  which it was. The scalar cursor and the multi-token scatter never take
  it (and are not tallied: they are not per-slot cursor writes)."""
  monkeypatch.setattr(ops, "pallas_kernels_enabled", lambda: kernels)
  monkeypatch.setattr(ops, "pallas_interpret", lambda: True)
  b, mx, c = shape
  buf = _draw(jax.random.PRNGKey(2), shape, dtype)
  val = _draw(jax.random.PRNGKey(3), (b, 1, c), dtype)
  idx = jnp.asarray([0, 5, MAX - 1, MAX], jnp.int32)
  with tfm.cursor_write_tally() as tally:
    out = tfm._cache_write(buf, val, idx, idx[:, None], mesh)
  assert tally == {"leaves": 1, "dma": int(dma)}, case
  np.testing.assert_array_equal(
      np.asarray(out, np.float32),
      np.asarray(_loop(buf, val[:, 0], idx), np.float32))
  with tfm.cursor_write_tally() as tally:
    tfm._cache_write(buf, val, jnp.int32(3), None, mesh)
    two = jnp.concatenate([val, val], axis=1)
    tfm._cache_write(buf, two, idx, idx[:, None] + jnp.arange(2), mesh)
  assert tally == {"leaves": 0, "dma": 0}, case
