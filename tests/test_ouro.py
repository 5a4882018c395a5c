"""A looped model (``TransformerConfig.loop_passes``: the layers run several
times a token over shared weights, a cache a pass, sandwich norms, a rotary
base of its own, an exit gate) through the plain forward, the cached decode
and the serving slab, against the plain reference in ``ouro_family.py`` (a
byte-for-byte copy of ``benchmarks/families/ouro.py``: float32, a full
forward with no cache, none of the program's code). Seeded weights, toy
widths, CPU.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ouro_family as fam
from tensorflowonspark_tpu import serving
from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.serving.slots import SlotDecoder

#: 3 layers x 4 passes at toy widths (the cell's rehearsal sizes)
TOY = dict(
    vocab_size=257, hidden_size=64, intermediate_size=96,
    num_hidden_layers=3, num_attention_heads=2, num_key_value_heads=2,
    head_dim=32, rms_norm_eps=1e-6, rope_theta=1e6, total_ut_steps=4,
    early_exit_threshold=1.0)
MAX_SEQ = 96
PASSES, LAYERS = 4, 3

#: float32 on both sides, the same mathematics: what is left is summation
#: order (logits are of order 1; measured 1e-5)
F32_ATOL = 2e-4


@pytest.fixture(scope="module")
def toy():
  cfg = fam.program_config(TOY, MAX_SEQ, dtype=jnp.float32)
  return dict(cfg=cfg, weights=fam.make_weights(7, TOY),
              params=fam.program_params(7, TOY))


def _tokens(seed, *shape):
  return np.random.default_rng(seed).integers(0, TOY["vocab_size"], shape,
                                              dtype=np.int32)


# -- the layer and the whole model against the reference ----------------------


def test_block_with_four_norms_equals_the_references_layer(toy):
  """One ``Block`` (``post_norm``: a norm before AND after each branch,
  rotary base 1e6) over a random stream, with norm scales that are NOT all
  ones so that each of the four is told apart."""
  cfg = toy["cfg"]
  keys = jax.random.split(jax.random.PRNGKey(3), 5)
  scales = {n: 1.0 + 0.3 * jax.random.normal(k, (LAYERS, 64))
            for n, k in zip(("ln1", "ln1_out", "ln2", "ln2_out"), keys)}
  weights = dict(toy["weights"], **scales)
  layer = dict(toy["params"]["layer_1"],
               **{n: {"scale": s[1]} for n, s in scales.items()})
  x = jax.random.normal(keys[4], (2, 24, 64))
  positions = jnp.broadcast_to(jnp.arange(24), (2, 24))
  got = tfm.Block(cfg).apply({"params": layer}, x, positions)
  want = fam.reference_layer(weights, x, TOY, 1)
  np.testing.assert_allclose(got, want, atol=F32_ATOL)
  # each norm matters: the same layer without the two output norms differs
  plain = tfm.Block(dataclasses.replace(cfg, post_norm=False)).apply(
      {"params": {k: v for k, v in layer.items() if "_out" not in k}}, x,
      positions)
  assert float(jnp.max(jnp.abs(plain - want))) > 0.1


def test_full_forward_equals_the_reference(toy):
  """3 layers x 4 passes, the final norm closing every pass, logits from
  the last pass at the published threshold."""
  toks = _tokens(1, 2, 40)
  got = jax.jit(lambda t: tfm.Transformer(toy["cfg"]).apply(
      {"params": toy["params"]}, t))(toks)
  np.testing.assert_allclose(
      got, fam.reference_logits(toy["weights"], toks, TOY), atol=F32_ATOL)


@pytest.mark.parametrize("threshold", [0.3, 0.6])
def test_full_forward_honours_a_lower_exit_threshold(toy, threshold):
  """Under 1.0 tokens leave at different passes (the gates decide) and each
  token's logits are its exit pass's: program and reference agree, and the
  result is not the last pass's."""
  conf = dict(TOY, early_exit_threshold=threshold)
  toks = _tokens(2, 2, 40)
  cfg = fam.program_config(conf, MAX_SEQ, dtype=jnp.float32)
  got, sown = tfm.Transformer(cfg).apply({"params": toy["params"]}, toks,
                                         mutable=["counters"])
  np.testing.assert_allclose(
      got, fam.reference_logits(toy["weights"], toks, conf), atol=F32_ATOL)
  leaves = np.asarray(sown["counters"]["exit_pass"][0])
  assert leaves.shape == (2, 40) and 1 <= leaves.min() < leaves.max() <= 4
  last = fam.reference_logits(toy["weights"], toks, TOY)
  assert float(jnp.max(jnp.abs(got - last))) > 0.1


# -- the exit distribution ----------------------------------------------------


def test_exit_distribution_sums_to_one_and_follows_the_threshold():
  gates = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(5), (4, 64)))
  p, at_one = fam.exit_distribution(gates, 1.0)
  np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-6)
  assert (np.asarray(at_one) == 4).all()       # the published threshold
  assert (np.asarray(fam.exit_distribution(gates, 1e-6)[1]) == 1).all()
  # in between: the first pass whose cumulative p reaches the threshold
  mid = np.asarray(fam.exit_distribution(gates, 0.5)[1])
  cum = np.cumsum(np.asarray(p), axis=0)
  want = np.where((cum >= 0.5).any(0), (cum >= 0.5).argmax(0) + 1, 4)
  np.testing.assert_array_equal(mid, want)
  # the program's own rule (gates of passes 1..n-1 only) is the same rule
  for thr in (1.0, 0.5, 1e-6):
    np.testing.assert_array_equal(
        tfm.exit_pass(list(gates[:-1]), thr),
        fam.exit_distribution(gates, thr)[1])


# -- one set of weights, a cache a pass, one cursor ---------------------------


def test_one_set_of_layer_weights_and_a_cache_a_pass(toy):
  cfg, params = toy["cfg"], toy["params"]
  model = tfm.Transformer(cfg)
  from flax.core import meta
  own = meta.unbox(jax.eval_shape(
      lambda: model.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))["params"]))
  assert jax.tree.structure(own) == jax.tree.structure(params)
  assert [a.shape for a in jax.tree.leaves(own)] \
      == [a.shape for a in jax.tree.leaves(params)]
  # ONE layer_0..layer_2 whatever the number of passes
  assert sorted(params) == ["embed", "exit_gate", "head", "layer_0",
                            "layer_1", "layer_2", "ln_f"]
  assert sum(x.size for x in jax.tree.leaves(params)) \
      == fam.param_count(TOY) == 138241
  dec = SlotDecoder(cfg, 2)
  slabs = dec.init_slabs()
  kv = [x for x in jax.tree.leaves(slabs) if x.ndim == 3]
  cursors = [x for x in jax.tree.leaves(slabs) if x.ndim == 1]
  assert len(kv) == PASSES * LAYERS * 2 and len(cursors) == LAYERS
  assert {x.shape for x in kv} == {(2, MAX_SEQ, 64)}
  assert sorted(slabs["layer_0"]["attn"]) == sorted(
      ["index"] + ["cached_%s_p%d" % (n, u) for n in "kv"
                   for u in range(PASSES)])
  # a token advances every cursor by ONE, not by the number of passes
  row, first = dec.prefill(params, _tokens(3, 11))
  slabs = dec.insert(slabs, row, 1)
  slabs, _ = dec.step(params, slabs, [0, first], [False, True])
  for x in jax.tree.leaves(slabs):
    if x.ndim == 1:
      np.testing.assert_array_equal(x, [0, 12])


def test_one_pass_is_todays_model():
  """``loop_passes`` 1 (the default) builds the tree, the cache and the
  programs a model had before the field existed: no gate, no per-pass
  names, four members out of ``step_many``."""
  cfg = tfm.TransformerConfig(vocab_size=61, num_layers=2, num_heads=2,
                              d_model=32, d_ff=64, max_seq_len=32,
                              dtype=jnp.float32, remat=False)
  assert (cfg.loop_passes, cfg.post_norm, cfg.rope_theta,
          cfg.loop_exit_threshold) == (1, False, 10000.0, 1.0)
  model = tfm.Transformer(cfg)
  toks = jnp.asarray(_tokens(4, 2, 12) % 61)
  variables = jax.jit(model.init)(jax.random.PRNGKey(0), toks)
  assert sorted(variables) == ["params"]          # nothing sown
  assert sorted(variables["params"]) == ["embed", "layer_0", "layer_1",
                                         "ln_f"]
  assert sorted(variables["params"]["layer_0"]) == ["attn", "ln1", "ln2",
                                                    "mlp"]
  assert sorted(tfm._zero_cache(model, 1)["layer_0"]["attn"]) \
      == ["cached_k", "cached_v", "index"]
  dec = SlotDecoder(cfg, 2)
  assert not dec.counted
  out = dec.step_many(variables["params"], dec.init_slabs(), [1, 2],
                      [True, True], [4, 4], 2)
  assert len(out) == 4 and dec.cursor_writes[2] == (2 * 2 * 2, 0)
  # and a field at its default lowers to the very same program
  spelled = dataclasses.replace(cfg, loop_passes=1, post_norm=False,
                                rope_theta=10000.0)
  lower = lambda c: jax.jit(lambda p, t: tfm.Transformer(c).apply(  # noqa: E731
      {"params": p}, t)).lower(variables["params"], toks).as_text()
  assert lower(cfg) == lower(spelled)


# -- through the serving slab -------------------------------------------------


def test_padded_prefill_then_step_many_equals_the_full_forward(toy):
  """Two prompts prefilled by the PADDED plan (11 and 23 tokens in chunks
  of 16 and 32: this model takes PR 27's path), inserted into two slots at
  different cursors and decoded by ``step_many`` at horizon 4: every served
  token is the reference's own first choice at its position, the tokens
  equal each prompt's own ``greedy_generate_kv`` decode, and the counters
  sum the live lanes' exit passes (4 a token) and contexts."""
  cfg, params = toy["cfg"], toy["params"]
  dec = SlotDecoder(cfg, 2)
  assert dec.padded_prefill and dec.counted
  assert dec.plan(11) == [(16, 11)] and dec.plan(23) == [(32, 23)]
  prompts = [_tokens(20, 11), _tokens(21, 23)]
  budget = 13                                    # 1 + three horizons of 4
  slabs = dec.init_slabs()
  last, got = np.zeros(2, np.int32), [[], []]
  for slot, p in enumerate(prompts):
    row, first = dec.prefill(params, p)
    slabs = dec.insert(slabs, row, slot)
    last[slot] = first
    got[slot].append(first)
  left = np.full(2, budget - 1, np.int32)
  totals = dict(context=0, exit_pass=0)
  for _ in range(3):
    slabs, toks, _, _, counts = dec.step_many(params, slabs, last, left > 0,
                                              left, 4)
    toks = np.asarray(toks)
    assert sorted(counts) == ["context", "exit_pass"]
    for name in totals:
      totals[name] += int(counts[name])
    for slot in range(2):
      got[slot].extend(toks[:, slot])
    last, left = toks[-1], left - 4
  # 384 x horizon at the cell's sizes: passes x layers x (K, V) x horizon
  assert dec.cursor_writes[4][0] == PASSES * LAYERS * 2 * 4
  live = 2 * (budget - 1)
  assert totals["exit_pass"] == 4 * live
  assert totals["context"] == sum(len(p) + j for p in prompts
                                  for j in range(budget - 1))
  for slot, p in enumerate(prompts):
    want = np.asarray(tfm.greedy_generate_kv(
        params, cfg, jnp.asarray(p)[None], budget))[0, len(p):]
    np.testing.assert_array_equal(np.asarray(got[slot]), want)
    seq = np.concatenate([p, want])[None]
    z = np.asarray(fam.reference_logits(toy["weights"], seq, TOY))[0]
    n = len(p)
    served = z[np.arange(n - 1, seq.shape[1] - 1), seq[0, n:]]
    # float32 on both sides: a served token is the reference's first choice
    # up to summation order (a near-tie may fall the other way by 1e-3)
    assert float(np.max(z[n - 1:-1].max(axis=-1) - served)) < 1e-3
  for x in jax.tree.leaves(slabs):
    if x.ndim == 1:
      np.testing.assert_array_equal(x, [11 + budget - 1, 23 + budget - 1])


def test_chunked_cached_decode_equals_the_full_forward_at_every_position(toy):
  """Prefill in chunks, then single tokens, through the scalar-cursor cache:
  the logits at EVERY position are the full forward's."""
  cfg, params = toy["cfg"], toy["params"]
  model = tfm.Transformer(cfg)
  toks = _tokens(6, 2, 38)
  want = fam.reference_logits(toy["weights"], toks, TOY)
  step = jax.jit(lambda c, t: model.apply(
      {"params": params, "cache": c}, t, decode=True, mutable=["cache"]))
  cache, outs, off = tfm._zero_cache(model, 2), [], 0
  for n in (32, 4, 1, 1):
    lg, mut = step(cache, toks[:, off:off + n])
    cache, off = mut["cache"], off + n
    outs.append(lg)
  np.testing.assert_allclose(jnp.concatenate(outs, axis=1), want,
                             atol=F32_ATOL)
  assert int(cache["layer_2"]["attn"]["index"]) == 38


def test_engine_counts_exit_passes_and_cursor_writes(toy):
  """Through ``ServingEngine``: every request's tokens are its own
  ``greedy_generate_kv`` decode; ``loop_exit_pass_sum`` is 4 a live token,
  the cursor writes 24 leaves x horizon a dispatch."""
  cfg, params = toy["cfg"], toy["params"]
  prompts = [_tokens(30 + i, n) for i, n in enumerate((9, 17, 9))]
  eng = serving.ServingEngine(params, cfg, num_slots=2, max_restarts=0,
                              horizon=4).start()
  try:
    outs = eng.generate(prompts, max_new_tokens=9)
  finally:
    eng.stop()
  # read with the loop stopped: generate() returns from inside the last
  # dispatch's harvest, before that dispatch is counted
  stats = dict(eng.stats)
  for p, out in zip(prompts, outs):
    want = np.asarray(tfm.greedy_generate_kv(
        params, cfg, jnp.asarray(p)[None], 9))[0]
    np.testing.assert_array_equal(np.asarray(out), want)
  assert stats["live_slot_steps"] > 0
  assert stats["loop_exit_pass_sum"] == 4 * stats["live_slot_steps"]
  assert stats["cursor_leaf_writes"] \
      == stats["decode_dispatches"] * PASSES * LAYERS * 2 * 4
  # one cache read a layer a PASS a step; the dense one on the CPU
  assert stats["decode_attn_reads"] \
      == stats["decode_dispatches"] * PASSES * LAYERS * 4
  assert stats["decode_attn_reads_ragged"] == 0
  assert stats["slab_in_place"] == stats["slab_dispatches"] > 0
  assert stats["prefill_chunks"] == stats["prefills"] == 3


# -- what must refuse this model ----------------------------------------------


@pytest.mark.parametrize("kwargs, mechanism, why", [
    (dict(page_size=16), "the paged KV pool", "ONE set of K/V pages"),
    (dict(page_size=16, prefix_pages=4), "the shared-prefix cache",
     "one set a layer"),
    (dict(spec_depth=2), "speculative decoding",
     "not a prefix of its passes"),
])
def test_engine_refuses_what_assumes_one_cache_a_layer(toy, kwargs,
                                                       mechanism, why):
  """Pages, prefix reuse and the shallow-exit draft assume ONE cache a layer
  run ONCE a token: a looped model is refused at construction, by the
  mechanism's name and with the reason, and never served corrupted."""
  with pytest.raises(ValueError, match=mechanism) as err:
    serving.ServingEngine(toy["params"], toy["cfg"], num_slots=2, **kwargs)
  assert why in str(err.value) and "run 4 times a token" in str(err.value)
  if "prefix_pages" not in kwargs:
    with pytest.raises(ValueError, match=mechanism):
      SlotDecoder(toy["cfg"], 2, **kwargs)


def test_a_threshold_under_one_is_refused_where_there_is_a_cache(toy):
  early = dataclasses.replace(toy["cfg"], loop_exit_threshold=0.5)
  with pytest.raises(ValueError, match="loop_exit_threshold=0.5") as err:
    SlotDecoder(early, 2)
  assert "writes no keys for its later passes" in str(err.value)
  with pytest.raises(ValueError, match="loop_exit_threshold=0.5"):
    serving.ServingEngine(toy["params"], early, num_slots=2)
  with pytest.raises(ValueError, match="cached decode path"):
    tfm.greedy_generate_kv(toy["params"], early,
                           jnp.asarray(_tokens(1, 1, 5)), 2)


def test_config_refuses_what_a_loop_cannot_mean(toy):
  with pytest.raises(ValueError, match="the paged KV pool"):
    dataclasses.replace(toy["cfg"], kv_page_size=16, kv_num_pages=8,
                        kv_pages_per_slot=6)
  with pytest.raises(ValueError, match="loop_passes"):
    tfm.TransformerConfig(loop_passes=0)
  with pytest.raises(ValueError, match="loop_exit_threshold"):
    tfm.TransformerConfig(loop_passes=2, loop_exit_threshold=0.0)
  with pytest.raises(ValueError, match="only attention"):
    tfm.TransformerConfig(num_layers=1, loop_passes=2, layer_types=("mla",))
  with pytest.raises(ValueError, match="shallow exit"):
    tfm.Transformer(toy["cfg"]).apply(
        {"params": toy["params"]}, _tokens(1, 1, 5), exit_layer=1)


# -- the control of the comparison that decides ``correct`` -------------------


def test_fp8_control_fails_the_comparison_at_toy_width(toy):
  """What the benchmark's check computes (how far a served token's float32
  reference logit lies below the reference's best), on the program's greedy
  tokens and on the fp8 reference's: the program reads zero to rounding, the
  control lies far beyond the rehearsal's limit (0.02)."""
  p = _tokens(50, 24)
  out = np.asarray(tfm.greedy_generate_kv(
      toy["params"], toy["cfg"], jnp.asarray(p)[None], 40))
  z = fam.reference_logits(toy["weights"], out, TOY)[0]
  best = z[:-1].max(axis=-1)
  served = jnp.take_along_axis(z[:-1], jnp.asarray(out)[0, 1:, None], 1)[:, 0]
  sound = float(jnp.max((best - served)[len(p) - 1:]))
  low = fam.reference_logits(toy["weights"], out, TOY, "fp8")[0]
  picked = jnp.take_along_axis(z[:-1], jnp.argmax(low[:-1], -1)[:, None],
                               1)[:, 0]
  control = float(jnp.max((best - picked)[len(p) - 1:]))
  assert sound < 1e-3 and control > 0.2, (sound, control)
