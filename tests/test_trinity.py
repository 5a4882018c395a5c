"""A model whose layers differ in their ATTENTION (``TransformerConfig.
layer_windows`` / ``layer_rope``: sliding-window layers that rotate beside
full layers that do not), with normed queries and keys, a gated attention
output, four norms a layer, a scaled embedding and held sparse experts,
through the plain forward, the cached decode and the serving slab (a RING
of the window's rows beside a whole-context leaf, ``kv_ring``), against the
plain reference in ``trinity_family.py`` (a byte-for-byte copy of
``benchmarks/families/trinity.py``: float32, a full forward with the window
as a mask, no cache, no ring, none of the program's code). Seeded weights,
toy widths, CPU.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import trinity_family as fam
from tensorflowonspark_tpu import ops, serving
from tensorflowonspark_tpu.models import experts as experts_mod
from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.parallel import expert_parallel as ep
from tensorflowonspark_tpu.serving.slots import SlotDecoder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "benchmarks", "configs",
                       "trinity-large-preview.json")) as _f:
  _FILE = json.load(_f)
#: the cell's rehearsal sizes: 1 dense + 4 expert layers (sliding, sliding,
#: full, sliding, sliding), hidden 64, 4 heads over 2 KV heads of 16, window
#: 8 (a ring of 16 rows), 4 held of 32 experts, 2 a token
TOY = dict({k: v for k, v in _FILE.items() if k != "rehearse"},
           **_FILE["rehearse"])
MAX_SEQ = 96
VOCAB = TOY["vocab_size"]

#: float32 on both sides, the same mathematics: what is left is summation
#: order (logits are of order 4; measured 7e-6)
F32_ATOL = 2e-4


def _toy(**changes):
  config = dict(TOY, **changes)
  return dict(config=config,
              cfg=fam.program_config(config, MAX_SEQ, dtype=jnp.float32),
              weights=fam.make_weights(7, config),
              params=fam.program_params(7, config))


@pytest.fixture(scope="module")
def toy():
  return _toy()


def _tokens(seed, *shape):
  return np.random.default_rng(seed).integers(0, VOCAB, shape, dtype=np.int32)


# -- the layers and the whole model against the reference ---------------------


@pytest.mark.parametrize("layer", [1, 2], ids=["window", "full"])
def test_a_layer_equals_the_references_layer(toy, layer):
  """One expert ``Block`` of each attention kind over a random stream 3
  windows long, with norm scales (the four of the layer AND the two on
  queries and keys) that are NOT all ones so that each is told apart: the
  window layer rotates and masks, the full layer does neither."""
  cfg, config = toy["cfg"], toy["config"]
  keys = jax.random.split(jax.random.PRNGKey(3), 7)
  names = ("ln1", "ln1_out", "ln2", "ln2_out", "q_norm", "k_norm")
  scales = {n: 1.0 + 0.3 * jax.random.normal(k, toy["weights"][n].shape)
            for n, k in zip(names, keys)}
  weights = dict(toy["weights"], **scales)
  tree = fam._to_program_tree(weights, fam.sizes(config))["layer_%d" % layer]
  x = jax.random.normal(keys[6], (2, 24, 64))
  positions = jnp.broadcast_to(jnp.arange(24), (2, 24))
  block = tfm.Block(cfg, None, False, "attn", "experts",
                    cfg.layer_windows[layer], cfg.layer_rope[layer])
  got = block.apply({"params": tree}, x, positions)
  want = fam.reference_layer(weights, x, config, layer)
  np.testing.assert_allclose(got, want, atol=F32_ATOL)
  # each part matters: the layer without the gate, without the normed
  # queries and keys, or as the OTHER kind of layer, differs
  for field, value in (("attn_gate", False), ("qk_norm", False)):
    less = dataclasses.replace(cfg, **{field: value})
    other = tfm.Block(less, None, False, "attn", "experts",
                      cfg.layer_windows[layer], cfg.layer_rope[layer]).apply(
                          {"params": tree}, x, positions)
    assert float(jnp.max(jnp.abs(other - want))) > 0.05, field
  swapped = tfm.Block(cfg, None, False, "attn", "experts",
                      cfg.layer_windows[3 - layer],
                      cfg.layer_rope[3 - layer]).apply({"params": tree}, x,
                                                       positions)
  assert float(jnp.max(jnp.abs(swapped - want))) > 0.05


def test_full_forward_equals_the_reference(toy):
  """1 + 4 layers over a sequence 5 windows long, the embedding scaled by
  sqrt(hidden), logits from the untied head."""
  toks = _tokens(1, 2, 40)
  got = jax.jit(lambda t: tfm.Transformer(toy["cfg"]).apply(
      {"params": toy["params"]}, t))(toks)
  want = fam.reference_logits(toy["weights"], toks, toy["config"])
  np.testing.assert_allclose(got, want, atol=F32_ATOL)
  # the scale matters: without it the logits are other numbers
  flat = jax.jit(lambda t: tfm.Transformer(dataclasses.replace(
      toy["cfg"], embed_scale=1.0)).apply({"params": toy["params"]}, t))(toks)
  assert float(jnp.max(jnp.abs(flat - want))) > 0.1


# -- the slab: a ring beside a whole-context leaf -----------------------------


def test_slab_leaves_are_rings_for_window_layers_and_rows_for_the_full_one(
    toy):
  """The one slab holds, under one cursor a layer, a ring of
  ``ring_rows(window)`` rows for each of the 4 window layers and
  ``max_seq_len`` rows for the full one; the prefill's row keeps every
  position in every layer."""
  cfg = toy["cfg"]
  assert cfg.ring_rows(8) == 16 and cfg.ring_layers == (0, 1, 3, 4)
  # whole blocks of the decode kernel from 128 rows on; a ring as long as
  # the row is no ring
  big = dataclasses.replace(cfg, max_seq_len=16384)
  assert big.ring_rows(4096) == 4096 and big.ring_rows(4000) == 4096
  assert big.ring_rows(16384) == 0 and big.ring_rows(0) == 0
  dec = SlotDecoder(cfg, 3)
  assert dec.ring_windows == (8, 8, 8, 8) and dec.slab_cfg.kv_ring
  slabs = dec.init_slabs()
  for i in range(5):
    leaves = slabs["layer_%d" % i]["attn"]
    rows = MAX_SEQ if i == 2 else 16
    assert leaves["cached_k"].shape == leaves["cached_v"].shape \
        == (3, rows, 2 * 16)
    assert leaves["index"].shape == (3,)
  row = tfm._zero_cache(dec.model, 1)
  assert all(x.shape == (1, MAX_SEQ, 32) for x in jax.tree.leaves(row)
             if x.ndim == 3)


@pytest.mark.parametrize("n", [11, 16, 37])
def test_insert_puts_position_p_in_row_p_mod_r(toy, n):
  """A positional row whose entry at position p IS p, inserted at cursor n:
  ring row r holds the newest position below n that is r modulo 16 (rows
  never written hold whatever: they lie outside ``min(n, 16)``); the full
  layer's leaf is the row itself."""
  dec = SlotDecoder(toy["cfg"], 2)
  mark = jnp.broadcast_to(jnp.arange(MAX_SEQ, dtype=jnp.float32)[None, :,
                                                                  None],
                          (1, MAX_SEQ, 32))
  row = jax.tree.map(
      lambda x: mark if x.ndim == 3 else jnp.asarray(n, x.dtype),
      tfm._zero_cache(dec.model, 1))
  slabs = dec.insert(dec.init_slabs(), row, 1)
  ring = np.asarray(slabs["layer_0"]["attn"]["cached_k"])[1, :, 0]
  for p in range(max(0, n - 16), n):
    assert ring[p % 16] == p, (p, ring)
  np.testing.assert_array_equal(
      np.asarray(slabs["layer_2"]["attn"]["cached_v"])[1, :, 0],
      np.arange(MAX_SEQ))
  assert [int(x[1]) for x in jax.tree.leaves(slabs) if x.ndim == 1] == [n] * 5


@pytest.mark.parametrize("window", [8, 16], ids=["ring16-window8",
                                                 "ring16-window16"])
def test_padded_prefill_then_a_wrapping_ring_equals_the_full_forward(window):
  """Two prompts, 11 tokens (below the ring) and 37 (past it: prefilled by
  the PADDED plan in chunks of 16, 16 and a tail of 5 padded to 8, rows past
  the cursor written), inserted and decoded by ``step_many`` at horizon 4 for
  12 tokens: the short slot's ring fills and wraps inside a horizon (cursor
  11 -> 23 over 16 rows), the long one wraps from the first step. Every served
  token is the reference's own first choice at its position, the tokens
  equal each prompt's own ``greedy_generate_kv`` decode (a positional cache,
  the window a mask), and the counters sum the live lanes' contexts and
  window rows. Window 16 fills the ring exactly: the ONE row outside the
  window is the row the step is about to overwrite."""
  toy = _toy(sliding_window=window)
  cfg, params = toy["cfg"], toy["params"]
  dec = SlotDecoder(cfg, 2)
  assert dec.padded_prefill and dec.counted and cfg.ring_rows(window) == 16
  buckets = (16, 8)
  assert dec.plan(37, buckets=buckets) == [(16, 16), (16, 16), (8, 5)]
  prompts = [_tokens(20, 11), _tokens(21, 37)]
  budget = 13                                    # 1 + three horizons of 4
  slabs = dec.init_slabs()
  last, got = np.zeros(2, np.int32), [[], []]
  for slot, p in enumerate(prompts):
    row, first = dec.prefill(params, p, buckets=buckets)
    slabs = dec.insert(slabs, row, slot)
    last[slot] = first
    got[slot].append(first)
  left = np.full(2, budget - 1, np.int32)
  totals = dict(context=0, window_context=0, held=0, touched=0)
  for _ in range(3):
    slabs, toks, _, _, counts = dec.step_many(params, slabs, last, left > 0,
                                              left, 4)
    toks = np.asarray(toks)
    assert sorted(counts) == sorted(totals)
    for name in totals:
      totals[name] += int(counts[name])
    for slot in range(2):
      got[slot].extend(toks[:, slot])
    last, left = toks[-1], left - 4
  # 10 leaves (K and V of 5 layers) and 5 reads a step, horizon 4
  assert dec.cursor_writes[4][0] == 10 * 4 and dec.attn_reads[4][0] == 5 * 4
  cursors = [len(p) + j for p in prompts for j in range(budget - 1)]
  assert totals["context"] == sum(cursors)
  assert totals["window_context"] == sum(min(c, window) for c in cursors)
  assert 0 < totals["touched"] <= totals["held"] <= 2 * 4 * len(cursors)
  # ONE reference forward over both rows (the shorter one padded with token
  # 0 behind its end, which a causal model's earlier positions do not see)
  seqs = np.zeros((2, 37 + budget), np.int32)
  for slot, p in enumerate(prompts):
    want = np.asarray(tfm.greedy_generate_kv(
        params, cfg, jnp.asarray(p)[None], budget))[0, len(p):]
    np.testing.assert_array_equal(np.asarray(got[slot]), want)
    seqs[slot, :len(p) + budget] = np.concatenate([p, want])
  logits = np.asarray(fam.reference_logits(toy["weights"], seqs,
                                           toy["config"]))
  for slot, p in enumerate(prompts):
    n, z = len(p), logits[slot, :len(p) + budget]
    served = z[np.arange(n - 1, n + budget - 1), seqs[slot, n:n + budget]]
    # float32 on both sides: a served token is the reference's first choice
    # up to summation order (a near-tie may fall the other way by 1e-3)
    assert float(np.max(z[n - 1:-1].max(axis=-1) - served)) < 1e-3
  for x in jax.tree.leaves(slabs):
    if x.ndim == 1:
      np.testing.assert_array_equal(x, [11 + budget - 1, 37 + budget - 1])


def test_chunked_cached_decode_equals_the_full_forward_at_every_position(toy):
  """Prefill in chunks, then single tokens, through the scalar-cursor cache
  (every position kept, the window a mask): the logits at EVERY position
  are the full forward's."""
  cfg, params = toy["cfg"], toy["params"]
  model = tfm.Transformer(cfg)
  toks = _tokens(30, 1, 45)
  cache = tfm._zero_cache(model, 1)
  step = jax.jit(lambda c, t: model.apply(
      {"params": params, "cache": c}, t, decode=True, mutable=["cache"]))
  outs, off = [], 0
  for seg in (16, 16, 8, 1, 1, 1, 1, 1):
    logits, mut = step(cache, toks[:, off:off + seg])
    cache, off = mut["cache"], off + seg
    outs.append(logits)
  want = fam.reference_logits(toy["weights"], toks, toy["config"])
  np.testing.assert_allclose(jnp.concatenate(outs, axis=1), want,
                             atol=F32_ATOL)


# -- the kernels' paths, in interpret mode ------------------------------------


@pytest.mark.parametrize("window,cursors", [
    (256, (0, 5, 255, 256, 257, 600, 1000)),      # ring == window
    (200, (0, 5, 199, 200, 256, 257, 900)),       # ring 256 > window
])
def test_ring_read_by_the_decode_kernel_equals_the_dense_path(
    monkeypatch, window, cursors):
  """``_cached_attention`` over a ring of 256 rows (bf16, 4 query heads over
  2 KV heads of 64) through ``ops.decode_attention`` (interpret mode) and
  through the dense contraction: the same numbers, at cursors below the
  ring, at its edge and wrapped. At ``ring == window`` the ONE row the step
  is about to overwrite (position ``cursor - 256``) is excluded: filling it
  with huge keys changes nothing."""
  b, h, hk, d, rows = len(cursors), 4, 2, 64, 256
  keys = jax.random.split(jax.random.PRNGKey(5), 5)
  q = jax.random.normal(keys[0], (b, 1, h, d)).astype(jnp.bfloat16)
  k, v = (jax.random.normal(kk, (b, 1, hk, d)).astype(jnp.bfloat16)
          for kk in keys[1:3])
  ck, cv = (jax.random.normal(kk, (b, rows, hk * d)).astype(jnp.bfloat16)
            for kk in keys[3:])
  cursor = jnp.asarray(cursors, jnp.int32)
  # the rows outside the window hold what would dominate any softmax
  skip = np.asarray(tfm._ring_skip(cursor, rows, window))
  for i in range(b):
    for j in range(int(skip[1, i])):
      ck = ck.at[i, (int(skip[0, i]) + j) % rows].set(50.0)
  args = dict(q_pos=cursor[:, None], window=window, lengths=cursor, ring=True)
  dense = tfm._cached_attention(q, k, v, ck, cv, **args)
  monkeypatch.setenv("TOS_PALLAS_INTERPRET", "0")
  monkeypatch.setattr(ops, "pallas_interpret", lambda: True)
  with tfm.decode_attention_tally() as reads:
    kernel = tfm._cached_attention(q, k, v, ck, cv, **args)
  assert reads == {"reads": 1, "ragged": 1, "ring": 1}
  # bf16 outputs of the same f32 mathematics: one rounding apart at most
  np.testing.assert_allclose(np.asarray(kernel, np.float32),
                             np.asarray(dense, np.float32), atol=2e-2)
  # against the plain softmax over each slot's window, position by position
  for i, c in enumerate(cursors):
    held = [p for p in range(max(0, c - rows), c) if p > c - window]
    kk = jnp.concatenate([ck[i, [p % rows for p in held]].reshape(-1, hk, d),
                          k[i]]).astype(jnp.float32)
    vv = jnp.concatenate([cv[i, [p % rows for p in held]].reshape(-1, hk, d),
                          v[i]]).astype(jnp.float32)
    s = jnp.einsum("hd,thd->ht", q[i, 0].astype(jnp.float32),
                   jnp.repeat(kk, h // hk, axis=1)) / d ** 0.5
    want = jnp.einsum("ht,thd->hd", jax.nn.softmax(s, -1),
                      jnp.repeat(vv, h // hk, axis=1))
    np.testing.assert_allclose(np.asarray(kernel[i, 0], np.float32), want,
                               atol=3e-2)


def test_a_later_chunk_of_a_long_row_goes_through_the_blocked_kernel(
    monkeypatch):
  """A row cache of several ``_ROW_BLOCK``s (32 rows here): the chunks after
  the first attend it through ``flash_attention_block`` (interpret mode),
  block by block from the window's lower edge, and the logits at every
  position are still the full forward's; the dense branch is not traced."""
  monkeypatch.setattr(tfm, "_ROW_BLOCK", 32)
  toy = _toy()
  cfg = dataclasses.replace(toy["cfg"], attention_impl="flash")
  model = tfm.Transformer(cfg)
  toks = _tokens(31, 1, 80)
  cache = tfm._zero_cache(model, 1)
  dense_calls = []
  real = tfm._cached_attention
  monkeypatch.setattr(tfm, "_cached_attention",
                      lambda *a, **kw: dense_calls.append(1) or real(*a, **kw))
  # one program for the five chunks (the cursor is traced)
  step = jax.jit(lambda c, t: model.apply(
      {"params": toy["params"], "cache": c}, t, decode=True,
      mutable=["cache"]))
  outs = []
  for off in range(0, 80, 16):
    logits, mut = step(cache, toks[:, off:off + 16])
    cache = mut["cache"]
    outs.append(logits)
  assert not dense_calls
  want = fam.reference_logits(toy["weights"], toks, toy["config"])
  np.testing.assert_allclose(jnp.concatenate(outs, axis=1), want,
                             atol=F32_ATOL)


# -- the share of an expert-parallel deployment --------------------------------


def test_the_eight_shares_add_up_to_the_uncut_layer(toy):
  """The sizing guide's share test: 32 experts over 8 chips, 4 each. The
  routed parts the 8 shares compute, plus the shared expert counted ONCE,
  equal the uncut reference layer (all 32 held); every token's 2 assignments
  are computed by exactly one share each."""
  uncut = dict(toy["config"], num_experts=32)
  z_all = fam.sizes(uncut)
  w_all = fam._layer_weights(fam.make_weights(7, uncut), z_all, 1)
  # the layer's own [held, ...] stacks out of the model's [layers, held, ...]
  w_all.update({n: w_all[n][w_all["exp_at"]]
                for n in ("exp_gate", "exp_up", "exp_down")})
  x = jax.random.normal(jax.random.PRNGKey(8), (2, 24, 64))
  want = fam._experts(x, w_all, z_all, "f32")
  flat = x.reshape(-1, 64)
  experts, weights = ep.route_sigmoid_topk(
      flat, w_all["router"], w_all["router_bias"], z_all["top_k"],
      z_all["scale"])
  total, assigned = 0.0, 0
  for share in range(8):
    part = slice(4 * share, 4 * share + 4)
    y, held = ep.held_experts_ffn(
        flat, experts, weights, w_all["exp_gate"][part], w_all["exp_up"][part],
        w_all["exp_down"][part], 4 * share)
    total, assigned = total + y.reshape(x.shape), assigned + int(held.sum())
    # and the reference, given the same share, agrees with the program's part
    z = dict(z_all, held=4, first=4 * share, shared=0)
    w = dict(w_all, **{n: w_all[n][part]
                       for n in ("exp_gate", "exp_up", "exp_down")})
    np.testing.assert_allclose(y.reshape(x.shape),
                               fam._experts(x, w, z, "f32"), atol=3e-5)
  shared = fam._swiglu(x, w_all["shared_gate"], w_all["shared_up"],
                       w_all["shared_down"], "f32")
  np.testing.assert_allclose(total + shared, want, atol=3e-5, rtol=3e-5)
  assert assigned == 2 * 24 * z_all["top_k"]
  # the layer's module is what computes a share in the program
  cfg = toy["cfg"]
  layer = toy["params"]["layer_1"]["moe"]
  got = experts_mod.HeldExperts(cfg).apply({"params": layer}, x)
  np.testing.assert_allclose(
      got, fam._experts(x, fam._layer_weights(
          toy["weights"], fam.sizes(toy["config"]), 1),
                        fam.sizes(toy["config"]), "f32"), atol=3e-5)


# -- the fields' defaults are today's programs --------------------------------


def test_the_per_layer_fields_at_their_defaults_are_todays_model():
  """A GPT-2 toy with the new fields spelled out at their defaults, and with
  per-layer values that SAY what the defaults mean (no window, every layer
  rotating): the same parameter tree, and the forward, a prefill chunk and
  the slab's ``step_many`` lower to the same text as the model that names
  none of them."""
  base = tfm.TransformerConfig(vocab_size=97, num_layers=2, num_heads=2,
                               d_model=32, d_ff=64, max_seq_len=48,
                               remat=False, dtype=jnp.float32)
  spelled = dataclasses.replace(
      base, layer_windows=(), layer_rope=(), qk_norm=False, attn_gate=False,
      embed_scale=1.0, kv_ring=False)
  assert spelled == base
  toks = jnp.zeros((1, 16), jnp.int32)
  params = tfm.Transformer(base).init(jax.random.PRNGKey(0), toks)["params"]

  def texts(cfg):
    dec = SlotDecoder(cfg, 2)
    assert (dec.slab_model is dec.model) == (not cfg.ring_layers)
    assert dec.counted == bool(cfg.ring_layers)
    fwd = jax.jit(lambda p, t: tfm.Transformer(cfg).apply({"params": p}, t))
    row = tfm._zero_cache(dec.model, 1)
    slabs = dec.init_slabs()
    n = jnp.zeros((2,), jnp.int32)
    return (fwd.lower(params, toks).as_text(),
            dec._prefill_fn.lower(params, row, toks, jnp.int32(9)).as_text(),
            dec.step_many_jit(4).lower(params, slabs, n, n > 0, n).as_text())

  want = texts(base)
  # the per-layer spec of the same model: scopes aside, the same programs
  said = dataclasses.replace(base, layer_windows=(0, 0),
                             layer_rope=(True, True))
  assert jax.tree.map(jnp.shape, tfm.Transformer(said).init(
      jax.random.PRNGKey(0), toks)["params"]) == jax.tree.map(jnp.shape,
                                                              params)
  assert not said.ring_layers
  for got, ref in zip(texts(said), want):
    assert got == ref
  # and a window changes them
  assert texts(dataclasses.replace(base, layer_windows=(8, 0)))[0] != want[0]


def test_config_checks_the_per_layer_fields():
  kw = dict(vocab_size=97, num_layers=2, num_heads=2, d_model=32, d_ff=64,
            max_seq_len=48)
  for bad in (dict(layer_windows=(8,)), dict(layer_windows=(8, -1)),
              dict(layer_rope=(True,))):
    with pytest.raises(ValueError, match="each of the 2 layers"):
      tfm.TransformerConfig(**kw, **bad)


# -- what a ring cannot take is refused, by name ------------------------------


@pytest.mark.parametrize("kwargs,reason", [
    (dict(page_size=16), "a pool of two lifetimes"),
    (dict(page_size=16, prefix_pages=4), "prefix sharing over window layers"),
    (dict(spec_depth=2), "speculation over a ring"),
])
def test_engine_refuses_what_a_ring_cannot_take(toy, kwargs, reason):
  with pytest.raises(ValueError, match=reason) as err:
    serving.ServingEngine(toy["params"], toy["cfg"], num_slots=2, **kwargs)
  assert "layer_windows" in str(err.value)


def test_slab_and_config_refuse_what_a_ring_cannot_take(toy):
  cfg = toy["cfg"]
  with pytest.raises(ValueError, match="a pool of two lifetimes"):
    dataclasses.replace(cfg, kv_page_size=16, kv_num_pages=8,
                        kv_pages_per_slot=6)
  with pytest.raises(ValueError, match="int8 ring"):
    SlotDecoder(dataclasses.replace(cfg, kv_cache_dtype="int8"), 2)
  with pytest.raises(ValueError, match="int8 ring"):
    dataclasses.replace(cfg, kv_cache_dtype="int8", kv_ring=True)
  with pytest.raises(ValueError, match="speculation over a ring"):
    SlotDecoder(cfg, 2, spec_depth=2)
  # a ring takes one token a step: a chunk into it is refused where traced
  ringed = tfm.Transformer(dataclasses.replace(cfg, kv_ring=True))
  cache = tfm._zero_cache(ringed, 1)
  with pytest.raises(ValueError, match="one token a step"):
    ringed.apply({"params": toy["params"], "cache": cache},
                 jnp.zeros((1, 4), jnp.int32), decode=True, mutable=["cache"])


# -- the engine ---------------------------------------------------------------


def test_engine_serves_past_the_window_and_counts(toy):
  """``ServingEngine`` on 2 slots with prompts below and past the window:
  every request's tokens are its own ``greedy_generate_kv`` decode, and the
  stats carry the step's sums (window rows never more than context rows, 10
  cursor writes and 5 reads a step)."""
  cfg, params = toy["cfg"], toy["params"]
  eng = serving.ServingEngine(params, cfg, num_slots=2, max_restarts=0,
                              buckets=(16, 8)).start()
  try:
    prompts = [_tokens(40 + i, n) for i, n in enumerate((5, 37, 20, 50))]
    rids = [eng.submit(p, max_new_tokens=9) for p in prompts]
    outs = [eng.result(r, timeout=300) for r in rids]
    stats = dict(eng.stats)
  finally:
    eng.stop()
  for p, out in zip(prompts, outs):
    want = np.asarray(tfm.greedy_generate_kv(
        params, cfg, jnp.asarray(p)[None], 9))[0]
    np.testing.assert_array_equal(np.asarray(out), want)
  assert 0 < stats["window_context_tokens"] < stats["live_context_tokens"]
  assert stats["window_context_tokens"] <= 8 * stats["live_slot_steps"]
  assert stats["moe_assignments_held"] > 0
  assert stats["cursor_leaf_writes"] == 10 * stats["steps"]
  assert stats["decode_attn_reads"] == 5 * stats["steps"]
  assert stats["engine_restarts"] == 0 and stats["replay_mismatches"] == 0


# -- the control of the comparison that decides ``correct`` -------------------


def test_fp8_control_fails_the_comparison_at_toy_width(toy):
  """What the benchmark's check computes (how far a served token's float32
  reference logit lies below the reference's best), on the program's greedy
  tokens and on the fp8 reference's: the program reads zero to rounding, the
  control lies far beyond the rehearsal's limit (0.02)."""
  p = _tokens(50, 24)
  out = np.asarray(tfm.greedy_generate_kv(
      toy["params"], toy["cfg"], jnp.asarray(p)[None], 40))
  z = fam.reference_logits(toy["weights"], out, toy["config"])[0]
  best = z[:-1].max(axis=-1)
  served = jnp.take_along_axis(z[:-1], jnp.asarray(out)[0, 1:, None], 1)[:, 0]
  sound = float(jnp.max((best - served)[len(p) - 1:]))
  low = fam.reference_logits(toy["weights"], out, toy["config"], "fp8")[0]
  picked = jnp.take_along_axis(z[:-1], jnp.argmax(low[:-1], -1)[:, None],
                               1)[:, 0]
  control = float(jnp.max((best - picked)[len(p) - 1:]))
  assert sound < 1e-3 and control > 0.2, (sound, control)
