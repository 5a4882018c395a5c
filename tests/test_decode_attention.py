"""``ops.decode_attention`` (the serving decode step's attention as a kernel
that stops at each slot's cursor) against the path it replaces:
``transformer._cached_attention``'s dense contraction over all ``max``
positions, in interpret mode on the CPU. What interpret mode cannot show
(the Mosaic lowering, the leaves staying in HBM beside the in-place cursor
write) is ``tests/test_mosaic_gate.py``'s; what only the chip shows (the
time, the served tokens) is PERF.md section 6, PR 31.
"""

from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu import ops
from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.ops.decode_attention import BLOCK
from tensorflowonspark_tpu.serving.slots import SlotDecoder

MAX = 2 * BLOCK
# cursors a serving slab really holds: 0 (a fresh or a free slot), 1, one
# under / at / one over a block's edge, max - 1, and max (a lane frozen at
# max_total: its whole slot is context)
CURSORS = (0, 1, BLOCK - 1, BLOCK, BLOCK + 1, MAX - 1, MAX)
BF16 = jnp.bfloat16


def _draw(heads, kv_heads, d, b=len(CURSORS), mx=MAX, seed=0):
  ks = jax.random.split(jax.random.PRNGKey(seed), 5)
  norm = lambda k, *shape: jax.random.normal(k, shape).astype(BF16)  # noqa: E731
  return (norm(ks[0], b, 1, heads, d), norm(ks[1], b, 1, kv_heads, d),
          norm(ks[2], b, 1, kv_heads, d), norm(ks[3], b, mx, kv_heads * d),
          norm(ks[4], b, mx, kv_heads * d))


def _dense(q, k, v, cached_k, cached_v, lengths):
  """The dense path's float32 result: ``_cached_attention`` casts its
  output to the query's dtype, so the query goes in as the float32 array
  that holds the same bf16 numbers (its three bf16 terms are then itself
  and two zeros: the same contraction)."""
  return tfm._cached_attention(q.astype(jnp.float32), k, v, cached_k,
                               cached_v, q_pos=lengths[:, None])[:, 0]


@pytest.mark.parametrize("heads,kv_heads,d", [
    (20, 20, 64), (16, 16, 128), (16, 4, 128), (8, 2, 64)],
    ids=["gpt2-large", "ouro", "gqa-128", "gqa-64"])
def test_equals_the_dense_path_on_ragged_cursors(heads, kv_heads, d):
  q, k, v, ck, cv = _draw(heads, kv_heads, d)
  lengths = jnp.asarray(CURSORS, jnp.int32)
  got = ops.decode_attention(q[:, 0], k[:, 0], v[:, 0], ck, cv, lengths,
                             interpret=True)
  assert got.shape == (len(CURSORS), heads, d) and got.dtype == jnp.float32
  # to f32 rounding: the blockwise softmax sums in another order
  np.testing.assert_allclose(np.asarray(got),
                             np.asarray(_dense(q, k, v, ck, cv, lengths)),
                             rtol=2e-5, atol=2e-6)
  # a slot at cursor 0 attends its own token only: its value, head by head
  np.testing.assert_array_equal(
      np.asarray(got[0]),
      np.asarray(jnp.repeat(v[0, 0], heads // kv_heads, axis=0), np.float32))


@pytest.mark.parametrize("heads,kv_heads,d", [(20, 20, 64), (16, 4, 128)],
                         ids=["gpt2-large", "gqa-128"])
def test_rows_at_and_above_a_cursor_never_reach_the_result(heads, kv_heads,
                                                          d):
  """NaN in every row at and above each slot's cursor (what a finished
  request left, what nobody wrote) changes no bit: such rows are neither
  summed (a masked score becomes -1e30 whatever it was, a V row past the
  cursor is zeroed before 0 x NaN can happen) nor, beyond the last live
  block, read at all."""
  q, k, v, ck, cv = _draw(heads, kv_heads, d, seed=1)
  lengths = jnp.asarray(CURSORS, jnp.int32)
  dead = jnp.arange(MAX)[None, :, None] >= lengths[:, None, None]
  run = lambda ck, cv: np.asarray(ops.decode_attention(  # noqa: E731
      q[:, 0], k[:, 0], v[:, 0], ck, cv, lengths, interpret=True))
  clean = run(ck, cv)
  poisoned = run(jnp.where(dead, jnp.nan, ck).astype(BF16),
                 jnp.where(dead, jnp.nan, cv).astype(BF16))
  assert np.isfinite(clean).all()
  np.testing.assert_array_equal(poisoned, clean)


def test_lengths_are_clamped_into_the_slot():
  """A cursor past ``max`` (a verify window's overshoot never reaches this
  path, but the clamp is the kernel's own) reads the whole slot, a negative
  one nothing."""
  q, k, v, ck, cv = _draw(4, 4, 128, b=2)
  run = lambda *lens: np.asarray(ops.decode_attention(  # noqa: E731
      q[:, 0], k[:, 0], v[:, 0], ck, cv, jnp.asarray(lens, jnp.int32),
      interpret=True))
  np.testing.assert_array_equal(run(MAX + 7, -3), run(MAX, 0))


@pytest.mark.parametrize("q_shape,q_dtype,cache_shape,cache_dtype,why", [
    ((4, 20, 64), BF16, (4, MAX, 1280), jnp.int8, "an int8 cache"),
    ((4, 20, 64), jnp.float32, (4, MAX, 1280), jnp.float32,
     "float32 leaves"),
    ((4, 20, 64), BF16, (4, MAX + 16, 1280), BF16,
     "a position axis off whole blocks"),
    ((4, 2, 32), BF16, (4, MAX, 64), BF16, "a minor axis off whole lanes"),
    ((4, 4, 96), BF16, (4, MAX, 384), BF16,
     "a head that neither divides nor fills 128 lanes"),
    ((4, 6, 128), BF16, (4, MAX, 512), BF16, "a ragged query group"),
    ((4, 20, 64), BF16, (8, MAX, 1280), BF16, "another slot count"),
    ((4, 1, 20, 64), BF16, (4, MAX, 1280), BF16, "a segment axis"),
])
def test_supports_refuses(q_shape, q_dtype, cache_shape, cache_dtype, why):
  assert not ops.decode_attention_supports(q_shape, q_dtype, cache_shape,
                                           cache_dtype), why
  if len(q_shape) == 3 and q_shape[0] == cache_shape[0] \
      and cache_dtype != jnp.int8:
    z = lambda shape, dt: jnp.zeros(shape, dt)  # noqa: E731
    kv = (q_shape[0], cache_shape[2] // q_shape[2], q_shape[2])
    with pytest.raises(ValueError, match="decode_attention takes"):
      ops.decode_attention(z(q_shape, q_dtype), z(kv, q_dtype),
                           z(kv, q_dtype), z(cache_shape, cache_dtype),
                           z(cache_shape, cache_dtype),
                           jnp.zeros((q_shape[0],), jnp.int32),
                           interpret=True)


def test_supports_takes_the_benchmark_cells_leaves():
  assert ops.decode_attention_supports((16, 20, 64), BF16, (16, 1024, 1280),
                                       BF16)
  assert ops.decode_attention_supports((8, 16, 128), BF16, (8, 512, 2048),
                                       BF16)


# what _cached_attention observes -> (kernels on, call kwargs, ragged?)
_MESH4 = SimpleNamespace(size=4)
_CASES = [
    ("the serving decode step on a chip", True, {}, True),
    ("a one-device mesh", True, dict(mesh=SimpleNamespace(size=1)), True),
    ("a tensor mesh: GSPMD cannot partition the call", True,
     dict(mesh=_MESH4), False),
    ("a sliding window", True, dict(window=64), False),
    ("the CPU: no Pallas kernels under auto", False, {}, False),
]


@pytest.mark.parametrize("case,kernels,kwargs,ragged", _CASES,
                         ids=[c[0] for c in _CASES])
def test_cached_attention_picks_the_lowering_from_what_it_observes(
    monkeypatch, case, kernels, kwargs, ragged):
  """Per-slot cursors and one token a slot: the kernel for bf16 leaves of
  whole lanes and blocks on one device where Pallas kernels are on, and the
  tally says so; for every other input the dense path's bits (the call
  without ``lengths``)."""
  monkeypatch.setattr(ops, "pallas_kernels_enabled", lambda: kernels)
  monkeypatch.setattr(ops, "pallas_interpret", lambda: True)
  q, k, v, ck, cv = _draw(4, 4, 128, b=3, seed=2)
  lengths = jnp.asarray([5, BLOCK, MAX - 1], jnp.int32)
  dense_kwargs = {k_: v_ for k_, v_ in kwargs.items() if k_ != "mesh"}
  dense = tfm._cached_attention(q, k, v, ck, cv, q_pos=lengths[:, None],
                                **dense_kwargs)
  with tfm.decode_attention_tally() as tally:
    got = tfm._cached_attention(q, k, v, ck, cv, q_pos=lengths[:, None],
                                lengths=lengths, **kwargs)
  assert tally == {"reads": 1, "ragged": int(ragged), "ring": 0}, case
  assert got.dtype == dense.dtype and got.shape == dense.shape
  if ragged:
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(dense, np.float32),
                               rtol=1e-2, atol=1e-2)     # two bf16 roundings
  else:
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(dense, np.float32))


def test_other_reads_keep_the_dense_path_and_are_not_tallied(monkeypatch):
  """The scalar cursor (``greedy_generate_kv``: no ``lengths``), a wide
  block (a prefill chunk, a speculative verify window) and an int8 cache
  never take the kernel; only the per-slot single-token ones are counted
  as reads at all."""
  monkeypatch.setattr(ops, "pallas_kernels_enabled", lambda: True)
  monkeypatch.setattr(ops, "pallas_interpret", lambda: True)
  monkeypatch.setattr(
      ops, "decode_attention",
      lambda *a, **k: pytest.fail("the kernel took a read it must not"))
  q, k, v, ck, cv = _draw(4, 4, 128, b=2, seed=3)
  lengths = jnp.asarray([9, 40], jnp.int32)
  two = lambda x: jnp.concatenate([x, x], axis=1)  # noqa: E731
  with tfm.decode_attention_tally() as tally:
    tfm._cached_attention(q, k, v, ck, cv, q_pos=jnp.asarray([[9]]))
    tfm._cached_attention(
        two(q), two(k), two(v), ck, cv, lengths=lengths,
        q_pos=lengths[:, None] + jnp.arange(2))
  assert tally == {"reads": 0, "ragged": 0, "ring": 0}
  scales = jnp.ones((2, MAX, 4), jnp.float32)
  with tfm.decode_attention_tally() as tally:
    tfm._cached_attention(q, k.astype(jnp.float32), v.astype(jnp.float32),
                          ck.astype(jnp.int8), cv.astype(jnp.int8),
                          q_pos=lengths[:, None], lengths=lengths,
                          k_scale=scales, v_scale=scales)
  assert tally == {"reads": 1, "ragged": 0, "ring": 0}


class TestThroughTheSlotDecoder:
  """Forced onto the kernel (interpret mode here) a ``SlotDecoder`` over a
  bf16 slab of whole blocks emits the dense path's tokens over three
  dispatches, counts one read a layer a step, and leaves a free lane's
  rows alone."""

  def _run(self, monkeypatch, kernels: bool):
    monkeypatch.setattr(ops, "pallas_kernels_enabled", lambda: kernels)
    cfg = tfm.TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=2, d_model=256, d_ff=256,
        max_seq_len=MAX, remat=False, dtype=BF16, layer_norm_impl="flax",
        attention_impl="dense")
    params = jax.tree.map(
        lambda x: x.astype(BF16),
        tfm.create_state(jax.random.PRNGKey(1), cfg, seq_len=16).params)
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, 64, (n,)).astype(np.int32)
               for n in (BLOCK - 2, 5)]
    dec = SlotDecoder(cfg, 3, pad_id=0)
    slabs, last = dec.init_slabs(), [0] * 3
    for slot, prompt in enumerate(prompts):
      row, last[slot] = dec.prefill(params, prompt)
      slabs = dec.insert(slabs, row, slot)
    # slot 0 crosses a block's edge inside the first dispatch; slot 1 runs
    # out of budget mid-horizon and is then a free lane; slot 2 is never
    # filled
    active, left, emitted = [True, True, False], [12, 6, 0], []
    for _ in range(3):
      slabs, toks, active, left = dec.step_many(params, slabs, last, active,
                                                left, 4)
      emitted.append(np.asarray(toks))
      last = list(emitted[-1][-1])
    return np.stack(emitted), dec.attn_reads[4], dec.cursor_writes[4]

  def test_same_tokens_and_the_reads_counted(self, monkeypatch):
    toks_dense, reads_dense, _ = self._run(monkeypatch, False)
    toks_ragged, reads_ragged, writes = self._run(monkeypatch, True)
    assert reads_dense == (2 * 4, 0, 0)           # a layer a step x horizon
    assert reads_ragged == (2 * 4, 2 * 4, 0)      # none of them over a ring
    assert writes == (4 * 4, 4 * 4)               # K and V of each beside it
    np.testing.assert_array_equal(toks_ragged, toks_dense)
    assert (toks_dense[:, :, 0] != 0).all()       # slot 0 ran all 12
    assert (toks_dense[2, :, 1] == 0).all()       # slot 1 stopped after 6
