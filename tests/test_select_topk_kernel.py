"""``ops.select_topk`` (the exact selection's threshold search in VMEM) in
interpret mode against the same search as XLA operations
(``models.transformer.select_topk`` handed the candidates as a mask): the two
masks are equal in every entry."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu import ops
from tensorflowonspark_tpu.models import transformer as tfm

# module and function share a name: ``ops.select_topk`` is the function
sk = importlib.import_module("tensorflowonspark_tpu.ops.select_topk")

ROWS, N, K = 16, 4096, 64


def _xla(scores, last, k):
  """The reference: the candidates as a mask keep the search XLA's."""
  valid = jnp.arange(scores.shape[-1]) <= jnp.asarray(last)[..., None]
  return np.asarray(tfm.select_topk(jnp.asarray(scores), valid, k))


def _kernel(scores, last, k):
  return np.asarray(ops.select_topk(jnp.asarray(scores), jnp.asarray(last), k,
                                    interpret=True))


def _scores(kind, shape, seed=0):
  rng = np.random.default_rng(seed)
  s = rng.normal(size=shape).astype(np.float32)
  if kind == "all_equal":
    s = np.full(shape, 0.25, np.float32)
  elif kind == "eight_levels":       # ties at every threshold
    s = (np.clip(np.round(s * 2), -4, 3) / 2).astype(np.float32)
  elif kind == "zeros_of_both_signs":
    s = np.where(rng.random(shape) < 0.7, 0.0, s).astype(np.float32)
    s[..., ::3] *= -1.0                # -0.0 and 0.0 are one score
  elif kind == "infinities":
    s = np.where(rng.random(shape) < 0.02, np.inf, s)
    s = np.where(rng.random(shape) < 0.02, -np.inf, s).astype(np.float32)
  return s


def _last(kind, rows, n, seed=1):
  block = sk._block(n)
  if kind == "zero":
    return np.zeros(rows, np.int32)
  if kind == "mid_block":
    return np.full(rows, block + block // 2 + 3, np.int32)
  if kind == "whole_row":
    return np.full(rows, n - 1, np.int32)
  if kind == "no_candidates":        # every other row has none
    return np.where(np.arange(rows) % 2, -1, n // 2).astype(np.int32)
  assert kind == "mixed"             # one tile: 0, a few, mid-row, the end
  mixed = np.random.default_rng(seed).integers(0, n, rows)
  mixed[:4] = (0, 5, n - 1, K - 1)
  return mixed.astype(np.int32)


@pytest.mark.parametrize("last", ["zero", "mid_block", "whole_row", "mixed",
                                  "no_candidates"])
@pytest.mark.parametrize("scores", ["normal", "all_equal", "eight_levels",
                                    "zeros_of_both_signs", "infinities"])
def test_the_kernels_mask_is_the_xla_searchs(scores, last):
  s, at = _scores(scores, (ROWS, N)), _last(last, ROWS, N)
  got = _kernel(s, at, K)
  np.testing.assert_array_equal(got, _xla(s, at, K))
  assert got.dtype == np.bool_
  # min(k, candidates) a row, none of them past the row's last candidate
  np.testing.assert_array_equal(got.sum(-1), np.minimum(at + 1, K))
  assert not (got & (np.arange(N) > at[:, None])).any()


@pytest.mark.parametrize("k", [1, 7, 8, 9, 2000, 5000])
def test_k_below_at_and_above_the_candidates(k):
  """Rows of 8 candidates beside rows of 2000 and of all 4096."""
  s = _scores("eight_levels", (ROWS, N), seed=k)
  at = np.resize(np.asarray([7, 1999, N - 1, 0], np.int32), ROWS)
  got = _kernel(s, at, k)
  np.testing.assert_array_equal(got, _xla(s, at, k))
  np.testing.assert_array_equal(got.sum(-1), np.minimum(at + 1, k))


@pytest.mark.parametrize("shape,k", [((3, 40, 1024), 16), ((2, 3, 5, 256), 8),
                                     ((70, 384), 200), ((1, 128), 128)])
def test_leading_axes_and_rows_off_a_whole_tile(shape, k):
  n = shape[-1]
  for kind in ("normal", "eight_levels"):
    s = _scores(kind, shape, seed=n)
    at = np.random.default_rng(n).integers(-1, n, shape[:-1]).astype(np.int32)
    got = _kernel(s, at, k)
    assert got.shape == shape
    np.testing.assert_array_equal(got, _xla(s, at, k))


def test_among_equal_scores_the_earlier_position_stays():
  """The 8th and 9th largest of a row are EQUAL: ``lax.top_k``'s order."""
  s = _scores("normal", (ROWS, N), seed=5)
  at = _last("whole_row", ROWS, N)
  order = np.argsort(-s[0])
  s[0, order[K]] = s[0, order[K - 1]]
  got = _kernel(s, at, K)
  tied = sorted((int(order[K - 1]), int(order[K])))
  assert got[0, tied[0]] and not got[0, tied[1]]
  _, idx = jax.lax.top_k(jnp.asarray(s), K)
  want = np.zeros(s.shape, bool)
  np.put_along_axis(want, np.asarray(idx), True, axis=-1)
  np.testing.assert_array_equal(got, want)


def test_a_last_candidate_outside_the_row_is_clipped():
  s = _scores("normal", (ROWS, N))
  at = np.resize(np.asarray([-5, N + 7, N - 1, -1], np.int32), ROWS)
  got = _kernel(s, at, K)
  np.testing.assert_array_equal(got, _xla(s, np.clip(at, -1, N - 1), K))
  assert not got[0].any() and got[1].sum() == K


@pytest.mark.parametrize("shape,dtype,mesh,why", [
    ((16, 48), jnp.float32, None, "a row that is not whole lanes"),
    ((16, 4096), jnp.bfloat16, None, "scores that are not float32"),
    ((4096,), jnp.float32, None, "no row axis"),
    ((0, 4096), jnp.float32, None, "no row"),
    ((16, 4096), jnp.float32, "two_devices", "a mesh"),
    ((64, 1 << 20), jnp.float32, None, "a tile past the VMEM budget"),
])
def test_supports_refuses(shape, dtype, mesh, why):
  if mesh:
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tensor",))
  assert not sk.supports(shape, dtype, mesh), why
  assert sk.supports((16, 4096), jnp.float32)
  assert sk.supports((1, 4096, 32768), jnp.float32)
  if len(shape) > 1 and shape[0] and not mesh:
    with pytest.raises(ValueError, match="select_topk takes float32 scores"):
      ops.select_topk(jnp.zeros(shape, dtype),
                      jnp.zeros(shape[:-1], jnp.int32), 4, interpret=True)


def test_one_last_candidate_a_row():
  with pytest.raises(ValueError, match="one last candidate a row"):
    ops.select_topk(jnp.zeros((16, 256)), jnp.zeros((8,), jnp.int32), 4,
                    interpret=True)


@pytest.mark.parametrize("case", ["kernel", "narrow", "bfloat16", "mesh",
                                  "mask"])
def test_the_models_selection_takes_the_kernel_where_it_can(case):
  """``models.transformer.select_topk`` handed each row's last candidate
  takes the kernel where ``supports`` says so and the XLA search elsewhere
  (and wherever it is handed a mask): the same mask, and the tally says which."""
  n = 48 if case == "narrow" else 256
  rng = np.random.default_rng(9)
  s = np.round(rng.normal(size=(6, n)) * 2).astype(np.float32) / 2
  at = rng.integers(0, n, 6).astype(np.int32)
  want = _xla(s, at, 8)
  scores = jnp.asarray(s, jnp.bfloat16 if case == "bfloat16" else jnp.float32)
  kw = {}
  if case == "mesh":
    from jax.sharding import Mesh
    kw["mesh"] = Mesh(np.asarray(jax.devices()[:2]), ("tensor",))
  second = jnp.arange(n) <= at[:, None] if case == "mask" else jnp.asarray(at)
  with tfm.index_select_tally() as tally:
    got = jax.jit(lambda a, b: tfm.select_topk(a, b, 8, **kw))(scores, second)
  np.testing.assert_array_equal(np.asarray(got), want)
  assert tally == {"selections": 1, "kernel": int(case == "kernel")}
  # outside a round nothing is noted
  tfm.select_topk(scores, second, 8, **kw)
  assert tally["selections"] == 1


# -- counted a program, added a dispatch --------------------------------------

_SPARSE = dict(vocab_size=97, num_layers=2, num_heads=4, d_model=32, d_ff=64,
               max_seq_len=128, remat=False, sparse_topk=8, index_heads=2,
               index_head_dim=8)


def _sparse_toy():
  from flax.core import meta
  cfg = tfm.TransformerConfig(**_SPARSE)
  # a model that selects is served, not trained: no create_state
  return cfg, meta.unbox(tfm.Transformer(cfg).init(
      jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"])


def _served(monkeypatch, kernel: bool):
  from tensorflowonspark_tpu.serving.slots import SlotDecoder
  if not kernel:
    monkeypatch.setattr(ops, "select_topk_supports", lambda *a: False)
  cfg, params = _sparse_toy()
  rng = np.random.RandomState(5)
  prompts = [rng.randint(1, 97, (n,)).astype(np.int32) for n in (27, 5)]
  dec = SlotDecoder(cfg, 3, pad_id=0)
  slabs, last = dec.init_slabs(), [0] * 3
  acc = dict(prefill_chunks=0, prefill_tokens=0, prefill_padded_tokens=0,
             t_prefill_sync_s=0.0)
  for slot, prompt in enumerate(prompts):
    row, last[slot] = dec.prefill(params, prompt, buckets=(16, 8), acc=acc)
    slabs = dec.insert(slabs, row, slot)
  toks = dec.step_many(params, slabs, last, [True, True, False], [8, 3, 0],
                       4)[1]
  return np.asarray(toks), dict(dec.index_selections), acc


def test_slot_decoder_counts_the_selections_a_program(monkeypatch):
  """Two layers that select: two searches a step (x horizon) and two a prefill
  chunk, all in the kernel (a row of 128 positions is whole lanes) or none;
  the same tokens either way."""
  toks_k, programs_k, acc_k = _served(monkeypatch, True)
  toks_x, programs_x, acc_x = _served(monkeypatch, False)
  assert programs_k == {("prefill", 16): (2, 2), ("prefill", 8): (2, 2),
                        ("step", 4): (8, 8)}
  assert programs_x == {("prefill", 16): (2, 0), ("prefill", 8): (2, 0),
                        ("step", 4): (8, 0)}
  # chunks of 16, 8 (padded: 11 real tokens left of 27) and 8
  assert acc_k["prefill_chunks"] == 3
  assert (acc_k["index_selections"], acc_k["index_selections_kernel"]) \
      == (6, 6)
  assert (acc_x["index_selections"], acc_x["index_selections_kernel"]) \
      == (6, 0)
  np.testing.assert_array_equal(toks_k, toks_x)
  assert (toks_k[:, 0] != 0).all()


def test_a_model_without_a_selection_counts_none():
  from tensorflowonspark_tpu.serving.slots import SlotDecoder
  cfg = tfm.TransformerConfig(**{
      k: v for k, v in _SPARSE.items()
      if k not in ("sparse_topk", "index_heads", "index_head_dim")})
  dec = SlotDecoder(cfg, 2)
  jax.eval_shape(dec.step_many_jit(2), jax.eval_shape(
      lambda: tfm.Transformer(cfg).init(
          jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]),
      jax.eval_shape(dec.init_slabs), jax.ShapeDtypeStruct((2,), jnp.int32),
      jax.ShapeDtypeStruct((2,), jnp.bool_),
      jax.ShapeDtypeStruct((2,), jnp.int32))
  assert dec.index_selections == {("step", 2): (0, 0)}


def test_engine_adds_them_a_dispatch():
  from tensorflowonspark_tpu import serving
  cfg, params = _sparse_toy()
  eng = serving.ServingEngine(params, cfg, num_slots=2, max_restarts=0,
                              buckets=(16, 8)).start()
  try:
    rng = np.random.RandomState(6)
    rids = [eng.submit(rng.randint(1, 97, (n,)).astype(np.int32),
                       max_new_tokens=5) for n in (5, 20, 9)]
    for r in rids:
      eng.result(r, timeout=300)
    stats = dict(eng.stats)
  finally:
    eng.stop()
  assert stats["index_selections"] == 2 * (stats["steps"]
                                           + stats["prefill_chunks"]) > 0
  assert stats["index_selections_kernel"] == stats["index_selections"]
