"""``ops.expert_product`` (the grouped product of held experts as one Pallas
kernel: only the rows that have a group go in, each touched matrix is streamed
once) in interpret mode on the CPU: against a dense product a group, through
``held_experts_ffn`` against the same through ``lax.ragged_dot``, under
``jax.grad``, counted by ``SlotDecoder`` and ``ServingEngine``; and the GPT-2
and Ouro serving programs, which have no expert layer, trace to what they were.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import ouro_family
# the serving programs' fingerprints, as the test of PR 40's fields takes them
from test_deepseek_v3 import _fingerprints, _rehearsal
from tensorflowonspark_tpu import ops, serving
from tensorflowonspark_tpu.models import experts as experts_mod
from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.parallel import expert_parallel as ep
from tensorflowonspark_tpu.serving.slots import SlotDecoder

# the module, not the function of the same name that ``ops`` exports
kernel_mod = importlib.import_module("tensorflowonspark_tpu.ops.expert_product")

BF16 = jnp.bfloat16
K, N = 256, 384

#: name -> (rows handed in, group sizes). The row tile is 128 (all rows where
#: there are fewer), so 70 rows a group straddle tiles and 300 rows are no
#: whole number of them; 37 rows are no whole packed sublane tile either
CASES = {
    "no_row_in_any_group": (96, [0, 0, 0, 0]),
    "one_row_a_group": (96, [1, 1, 1, 1]),
    "three_rows_a_group": (96, [3, 3, 3, 3]),
    "seventy_rows_a_group": (300, [70, 70, 70]),
    "every_row_held": (256, [100, 28, 128]),
    "an_empty_first_group": (96, [0, 5, 3]),
    "an_empty_middle_group": (96, [5, 0, 3]),
    "an_empty_last_group": (96, [5, 3, 0]),
    "rows_no_multiple_of_the_tile": (300, [70, 0, 130, 1, 0]),
    "rows_no_multiple_of_a_sublane_tile": (37, [3, 9]),
    "a_group_over_three_tiles": (400, [1, 290, 0, 60]),
}


def _operands(m, sizes, seed=0, dtype=BF16):
  rng = np.random.default_rng(seed)
  lhs = jnp.asarray(rng.standard_normal((m, K)), dtype)
  rhs = jnp.asarray(rng.standard_normal((len(sizes), K, N)) * K ** -0.5, BF16)
  return lhs, rhs, jnp.asarray(sizes, jnp.int32)


def _dense(lhs, rhs, sizes):
  """Each group's rows times that group's matrix, in float64; zeros behind
  the last group."""
  out = np.zeros((lhs.shape[0], rhs.shape[2]))
  start = 0
  for g, n in enumerate(sizes):
    out[start:start + n] = np.asarray(lhs[start:start + n], np.float64) \
        @ np.asarray(rhs[g], np.float64)
    start += n
  return out


@pytest.mark.parametrize("terms", [1, 3])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_equals_a_dense_product_a_group(case, terms):
  """bf16 rows (``terms`` 1) or float32 rows as three bf16 terms a row, rows
  and group sizes times 3 (what ``held_experts_ffn`` does with ``split``):
  the held rows are the dense product to f32 summation order, the rows behind
  the last group EXACTLY zero."""
  m, sizes = CASES[case]
  lhs, rhs, sz = _operands(m, sizes, dtype=BF16 if terms == 1 else jnp.float32)
  assert ops.expert_product_supports((m * terms, K), BF16, rhs.shape,
                                     rhs.dtype)
  parts = tfm._bf16_terms(lhs)
  assert len(parts) == terms
  stacked = jnp.stack(parts, axis=1).reshape(-1, K)
  got = ops.expert_product(stacked, rhs, sz * terms, interpret=True)
  assert got.shape == (m * terms, N) and got.dtype == jnp.float32
  got = np.asarray(got).reshape(m, terms, N).sum(axis=1)
  held = sum(sizes)
  np.testing.assert_allclose(got[:held], _dense(lhs, rhs, sizes)[:held],
                             atol=2e-5 if terms == 1 else 2e-6, rtol=0)
  assert not got[held:].any()


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_equals_ragged_dot_on_the_held_rows(case):
  m, sizes = CASES[case]
  lhs, rhs, sz = _operands(m, sizes, seed=1)
  got = ops.expert_product(lhs, rhs, sz, interpret=True)
  want = lax.ragged_dot(lhs, rhs, sz, preferred_element_type=jnp.float32)
  held = sum(sizes)
  np.testing.assert_allclose(got[:held], want[:held], atol=2e-5, rtol=0)


@pytest.mark.parametrize("m,sizes,most", [
    (300, [70, 0, 130, 1, 0], 3 + 5 - 1),
    (96, [0, 0, 0], 1 + 3 - 1),
    (400, [1, 290, 0, 60], 4 + 4 - 1),
])
def test_the_pairs_are_the_tiles_and_groups_that_intersect(m, sizes, most):
  tm = 128
  starts, groups, tiles, count = kernel_mod.pairs(
      jnp.asarray(sizes, jnp.int32), m, tm)
  edges = np.concatenate([[0], np.cumsum(sizes)])
  want = [(g, t) for g in range(len(sizes)) for t in range(-(-m // tm))
          if max(edges[g], t * tm) < min(edges[g + 1], (t + 1) * tm)]
  assert len(groups) == len(tiles) == most
  np.testing.assert_array_equal(starts, edges)
  if not want:                 # one pair that stores a tile of zeros
    assert int(count) == 1 and sizes[int(groups[0])] == 0
    return
  assert int(count) == len(want)
  assert list(zip(np.asarray(groups)[:len(want)].tolist(),
                  np.asarray(tiles)[:len(want)].tolist())) == want


def test_supports_reads_dtypes_and_shapes():
  yes = ((96, 256), BF16, (4, 256, 384), BF16)
  assert ops.expert_product_supports(*yes)
  for no in [
      ((96, 256), jnp.float32, (4, 256, 384), BF16),       # unsplit rows
      ((96, 256), BF16, (4, 256, 384), jnp.float32),       # a float32 stack
      ((96, 192), BF16, (4, 192, 384), BF16),              # K off whole lanes
      ((96, 256), BF16, (4, 256, 64), BF16),               # N off whole lanes
      ((96, 256), BF16, (4, 128, 384), BF16),              # K against K
      ((0, 256), BF16, (4, 256, 384), BF16),
      ((96, 256), BF16, (256, 384), BF16),
      ((96, 1 << 17), BF16, (4, 1 << 17, 128), BF16),      # no block fits
  ]:
    assert not ops.expert_product_supports(*no), no
  with pytest.raises(ValueError, match="expert_product takes bf16 rows"):
    ops.expert_product(jnp.zeros((8, 192), BF16), jnp.zeros((2, 192, 128), BF16),
                       jnp.zeros((2,), jnp.int32), interpret=True)


@pytest.mark.parametrize("shape,tiles", [
    # the four cells' decode step and largest chunk, gate and down
    ((96, 3072, 3072), (96, 1024)), ((8192, 3072, 3072), (128, 1024)),
    ((384, 4096, 2048), (128, 1024)), ((16384, 2048, 4096), (128, 2048)),
    ((192, 7168, 2048), (128, 512)), ((16384, 2048, 7168), (128, 1792)),
    ((1152, 2304, 1024), (128, 1024)), ((12288, 1024, 2304), (128, 2304)),
    ((37, 256, 384), (48, 384)),
])
def test_tiles_follow_the_shape(shape, tiles):
  m, k, n = shape
  assert kernel_mod._tiles(m, k, n) == tiles
  assert ops.expert_product_supports((m, k), BF16, (16, k, n), BF16)


# -- through held_experts_ffn -------------------------------------------------


def _layer(seed, t=40, d=128, f=256, total=16, held=4, first=4, top_k=4,
           dtype=BF16):
  ks = jax.random.split(jax.random.PRNGKey(seed), 6)
  x = jax.random.normal(ks[0], (t, d), jnp.float32)
  router = jax.random.normal(ks[1], (d, total), jnp.float32) * d ** -0.5
  experts, weights = ep.route_sigmoid_topk(x, router, jnp.zeros((total,)),
                                           top_k)
  gate, up = (jax.random.normal(k, (held, d, f), jnp.float32) * d ** -0.5
              for k in ks[2:4])
  down = jax.random.normal(ks[4], (held, f, d), jnp.float32) * f ** -0.5
  return (x, experts, weights, gate.astype(dtype), up.astype(dtype),
          down.astype(dtype), first)


class _TwoDevices:
  """What ``held_experts_ffn`` reads of a mesh that partitions the call."""
  size = 2


@pytest.mark.parametrize("split", [None, tfm._bf16_terms],
                         ids=["one_term", "three_terms"])
def test_held_experts_ffn_through_the_kernel_equals_ragged_dot(split):
  """The same layer by both lowerings (a mesh of two devices keeps
  ``ragged_dot``): within bf16 rounding of the hidden activations, the held
  mask identical, and the tally says which took the kernel."""
  args = _layer(3)
  tally_k, tally_r = (dict(products=0, kernel=0) for _ in range(2))
  y_k, held_k = ep.held_experts_ffn(*args, split=split, tally=tally_k)
  y_r, held_r = ep.held_experts_ffn(*args, split=split, mesh=_TwoDevices(),
                                    tally=tally_r)
  assert tally_k == dict(products=3, kernel=3)
  assert tally_r == dict(products=3, kernel=0)
  np.testing.assert_array_equal(held_k, held_r)
  assert 0 < int(held_k.sum()) < held_k.size      # some held, some elsewhere
  assert float(jnp.abs(y_r).max()) > 0.1
  np.testing.assert_allclose(y_k, y_r, atol=2e-2 if split is None else 1e-4,
                             rtol=0)


def _todays_held_experts_ffn(x, experts, weights, gate, up, down, first):
  """``held_experts_ffn`` as it was before the kernel (PR 40), for a float32
  stack: every product ``lax.ragged_dot`` at ``Precision.HIGHEST``."""
  t, k = experts.shape
  n_held = gate.shape[0]
  local = experts - first
  held = jnp.logical_and(local >= 0, local < n_held)
  key = jnp.where(held, local, n_held).reshape(-1)
  order = jnp.argsort(key, stable=True)
  sizes = jnp.bincount(key, length=n_held + 1)[:n_held].astype(jnp.int32)
  rows = jnp.take(x, order // k, axis=0)

  def grouped(lhs, rhs):
    return lax.ragged_dot(lhs.astype(rhs.dtype), rhs, sizes,
                          preferred_element_type=jnp.float32,
                          precision=lax.Precision.HIGHEST)

  hidden = jax.nn.silu(grouped(rows, gate)) * grouped(rows, up)
  out = grouped(hidden, down)
  w = jnp.where(held, weights, 0.0).reshape(-1)[order]
  out = jnp.where((w > 0)[:, None], out * w[:, None], 0.0)
  return jnp.take(out, jnp.argsort(order), axis=0).reshape(t, k, -1).sum(axis=1)


def test_a_float32_stack_still_gives_todays_numbers():
  args = _layer(4, dtype=jnp.float32)
  tally = dict(products=0, kernel=0)
  y, _ = ep.held_experts_ffn(*args, tally=tally)
  assert tally == dict(products=3, kernel=0)
  np.testing.assert_array_equal(y, _todays_held_experts_ffn(*args))


def _experts_cfg(dtype, **kw):
  base = dict(vocab_size=64, num_layers=2, num_heads=2, d_model=128, d_ff=256,
              max_seq_len=64, remat=False, dtype=dtype, norm="rms",
              mlp_act="swiglu", ffn_types=("mlp", "experts"),
              experts_total=16, experts_held=4, experts_first=4,
              experts_top_k=4, experts_d_ff=128, experts_shared=1)
  base.update(kw)
  return tfm.TransformerConfig(**base)


@pytest.mark.parametrize("dtype", [jnp.float32, BF16], ids=["float32", "bf16"])
def test_a_gradient_through_held_experts(dtype, monkeypatch):
  """``jax.grad`` through ``HeldExperts``: a float32 layer takes no kernel
  and gives the gradient it gave (bit for bit); a bf16 layer takes the kernel
  forward and ``ragged_dot``'s backward, and its gradient is the one the
  layer gives with ``ragged_dot`` in both directions, to bf16 rounding."""
  cfg = _experts_cfg(dtype)
  layer = experts_mod.HeldExperts(cfg)
  x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, cfg.d_model),
                        jnp.float32).astype(dtype)
  params = layer.init(jax.random.PRNGKey(1), x)["params"]

  def loss(p):
    return jnp.sum(layer.apply({"params": p}, x).astype(jnp.float32) ** 2)

  with tfm.expert_product_tally() as tally:
    got = jax.grad(loss)(params)
  assert tally == dict(products=3, kernel=0 if dtype == jnp.float32 else 3)
  monkeypatch.setattr(ops, "expert_product_supports", lambda *a: False)
  want = jax.grad(loss)(params)
  for name in ("gate", "up", "down", "router"):
    g, w = np.asarray(got[name]), np.asarray(want[name])
    assert np.isfinite(g).all() and np.abs(g).max() > 0
    if dtype == jnp.float32:
      np.testing.assert_array_equal(g, w)
    else:
      np.testing.assert_allclose(g, w, atol=0.03 * np.abs(w).max(), rtol=0)


# -- counted a program, added a dispatch --------------------------------------


def _served(monkeypatch, kernel: bool):
  if not kernel:
    monkeypatch.setattr(ops, "expert_product_supports", lambda *a: False)
  cfg = _experts_cfg(BF16, layer_norm_impl="flax", attention_impl="dense")
  params = jax.tree.map(
      lambda x: x.astype(BF16),
      tfm.create_state(jax.random.PRNGKey(1), cfg, seq_len=16).params)
  rng = np.random.RandomState(5)
  prompts = [rng.randint(1, 64, (n,)).astype(np.int32) for n in (11, 5)]
  dec = SlotDecoder(cfg, 3, pad_id=0)
  slabs, last = dec.init_slabs(), [0] * 3
  acc = dict(prefill_chunks=0, prefill_tokens=0, prefill_padded_tokens=0,
             t_prefill_sync_s=0.0, expert_products=0,
             expert_products_kernel=0)
  for slot, prompt in enumerate(prompts):
    row, last[slot] = dec.prefill(params, prompt, buckets=(16, 8), acc=acc)
    slabs = dec.insert(slabs, row, slot)
  # slot 1 runs out of budget inside the horizon, slot 2 is never filled; a
  # model that counts returns its step's sums as a fifth member
  toks = dec.step_many(params, slabs, last, [True, True, False], [8, 3, 0],
                       4)[1]
  return np.asarray(toks), dict(dec.expert_products), acc


def test_slot_decoder_counts_the_products_a_program(monkeypatch):
  """One expert layer of two: three products a step (x horizon) and three a
  prefill chunk, all by the kernel or none; the same tokens either way."""
  toks_k, programs_k, acc_k = _served(monkeypatch, True)
  toks_r, programs_r, acc_r = _served(monkeypatch, False)
  assert programs_k == {("prefill", 16): (3, 3), ("prefill", 8): (3, 3),
                        ("step", 4): (12, 12)}
  assert programs_r == {("prefill", 16): (3, 0), ("prefill", 8): (3, 0),
                        ("step", 4): (12, 0)}
  assert (acc_k["expert_products"], acc_k["expert_products_kernel"]) == (6, 6)
  assert (acc_r["expert_products"], acc_r["expert_products_kernel"]) == (6, 0)
  np.testing.assert_array_equal(toks_k, toks_r)
  assert (toks_k[:, 0] != 0).all()


def test_engine_adds_them_a_dispatch():
  cfg = _experts_cfg(BF16, layer_norm_impl="flax", attention_impl="dense")
  params = jax.tree.map(
      lambda x: x.astype(BF16),
      tfm.create_state(jax.random.PRNGKey(1), cfg, seq_len=16).params)
  eng = serving.ServingEngine(params, cfg, num_slots=2, max_restarts=0,
                              buckets=(16, 8)).start()
  try:
    rng = np.random.RandomState(6)
    rids = [eng.submit(rng.randint(1, 64, (n,)).astype(np.int32),
                       max_new_tokens=5) for n in (5, 20, 9)]
    for r in rids:
      eng.result(r, timeout=300)
    stats = dict(eng.stats)
  finally:
    eng.stop()
  assert stats["expert_products"] == 3 * (stats["steps"]
                                          + stats["prefill_chunks"]) > 0
  assert stats["expert_products_kernel"] == stats["expert_products"]


# -- programs without an expert layer are what they were ----------------------

#: the jaxpr of the decode step (2 slots, horizon 2) and of a padded 16-token
#: prefill chunk at the GPT-2 and Ouro cells' rehearsal sizes, hashed at the
#: parent of PR 41 (commit 6a7c83f)
PARENTS_PROGRAMS = {
    "gpt2.prefill16": "916ba60cd536afd2",
    "gpt2.step_many": "9bc8498a1247bdd7",
    "ouro.prefill16": "5178ac7fa4b5a06d",
    "ouro.step_many": "6b39c312eeb11892",
}


def _gpt2_cfg():
  c = _rehearsal("gpt2-large")
  return tfm.TransformerConfig(
      vocab_size=c["vocab_size"], num_layers=c["n_layer"],
      num_heads=c["n_head"], d_model=c["n_embd"], d_ff=4 * c["n_embd"],
      max_seq_len=128, remat=False, dtype=BF16)


def _ouro_cfg():
  return ouro_family.program_config(_rehearsal("ouro-2.6b"), 128, dtype=BF16)


@pytest.mark.parametrize("model,make", [("gpt2", _gpt2_cfg),
                                        ("ouro", _ouro_cfg)])
def test_programs_without_an_expert_layer_are_the_parents(model, make):
  """The traffic of the GPT-2 and Ouro cells bypasses the mechanism: their
  decode step and prefill chunk trace to the jaxpr they traced to at the
  parent, and count no product."""
  cfg = make()
  assert _fingerprints(model, cfg) == {
      k: v for k, v in PARENTS_PROGRAMS.items() if k.startswith(model + ".")}
  dec = SlotDecoder(cfg, 2)
  jax.eval_shape(dec.step_many_jit(2), jax.eval_shape(
      lambda: tfm.Transformer(cfg).init(
          jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]),
      jax.eval_shape(dec.init_slabs), jax.ShapeDtypeStruct((2,), jnp.int32),
      jax.ShapeDtypeStruct((2,), jnp.bool_),
      jax.ShapeDtypeStruct((2,), jnp.int32))
  assert dec.expert_products == {("step", 2): (0, 0)}
