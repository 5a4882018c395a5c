"""Latent attention in DeepSeek-V3's form (``TransformerConfig.mla_q_rank`` /
``mla_rope`` / ``rope_yarn_*``: the query through a rank, the queries' rope
part and the latent's shared key part rotated at YaRN's frequencies, the
softmax scale times ``m^2``) and a router with a GROUP limit
(``experts_groups`` / ``experts_groups_kept``) beside a shared expert, through
the plain forward, the cached decode and the serving slab (one latent leaf a
layer), against the plain reference in ``deepseek_v3_family.py`` (a
byte-for-byte copy of ``benchmarks/families/deepseek_v3.py``: float32, a full
forward with keys and values expanded a head, no cache, nothing absorbed, none
of the program's code). Seeded weights, toy widths, CPU.
"""

import dataclasses
import hashlib
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepseek_v3_family as fam
from tensorflowonspark_tpu import ops, serving
from tensorflowonspark_tpu.models import experts as experts_mod
from tensorflowonspark_tpu.models import mla as mla_mod
from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.parallel import expert_parallel as ep
from tensorflowonspark_tpu.serving.slots import SlotDecoder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _file(name):
  with open(os.path.join(REPO, "benchmarks", "configs", name + ".json")) as f:
    return json.load(f)


def _rehearsal(name):
  f = _file(name)
  return dict({k: v for k, v in f.items() if k != "rehearse"}, **f["rehearse"])


_FILE = _file("deepseek-v3")
PUBLISHED = {k: v for k, v in _FILE.items() if k != "rehearse"}
#: the cell's rehearsal sizes: 1 dense + 2 expert layers, hidden 64, 4 heads of
#: nope 16 / rope 16 / values 16, query rank 24, latent rank 32 (a leaf of 48
#: numbers in 128 lanes), 4 held of 32 experts in 4 groups of 8, 2 groups and
#: 4 experts a token, YaRN over 8 frequencies with original 64
TOY = _rehearsal("deepseek-v3")
MAX_SEQ = 96
_HI = jax.lax.Precision.HIGHEST
VOCAB = TOY["vocab_size"]

#: float32 on both sides, the same mathematics: what is left is summation
#: order (logits are of order 4; measured 6e-6)
F32_ATOL = 2e-4


_WEIGHTS = {}


def _toy(max_seq=MAX_SEQ):
  # the weights do not depend on the cache's length: made once a module
  if not _WEIGHTS:
    _WEIGHTS.update(weights=fam.make_weights(7, TOY),
                    params=fam.program_params(7, TOY))
  return dict(_WEIGHTS, config=TOY,
              cfg=fam.program_config(TOY, max_seq, dtype=jnp.float32))


@pytest.fixture(scope="module")
def toy():
  return _toy()


def _tokens(seed, *shape):
  return np.random.default_rng(seed).integers(0, VOCAB, shape, dtype=np.int32)


# -- the configuration and its numbers ----------------------------------------


def test_the_toy_is_the_published_model_at_toy_widths(toy):
  cfg = toy["cfg"]
  assert cfg.layer_types == ("mla",) * 3
  assert cfg.ffn_types == ("mlp", "experts", "experts")
  assert (cfg.mla_q_rank, cfg.mla_kv_rank, cfg.mla_rope) == (24, 32, True)
  assert (cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim) == (16, 16, 16)
  assert (cfg.experts_groups, cfg.experts_groups_kept) == (4, 2)
  assert (cfg.experts_total, cfg.experts_held, cfg.experts_top_k) == (32, 4, 4)
  assert cfg.experts_shared == 1 and cfg.experts_scale == 2.5
  assert (cfg.rope_yarn_factor, cfg.rope_yarn_original) == (40.0, 64)
  # the published sizes
  z = fam.sizes(PUBLISHED)
  assert (z["heads"], z["q_rank"], z["kv_rank"]) == (128, 1536, 512)
  assert (z["nope"], z["rope"], z["v_dim"]) == (128, 64, 128)
  assert (z["routed"], z["groups"], z["groups_kept"], z["top_k"]) \
      == (256, 8, 4, 8)
  assert (z["held"], z["layers"], z["dense_layers"], z["vocab"]) \
      == (16, 5, 1, 16256)
  assert fam.param_count(PUBLISHED) == _FILE["parameters_as_built"] \
      == 4567097344
  mla = toy["params"]["layer_1"]["mla"]
  assert sorted(mla) == ["kv_norm", "kva", "kvb", "out", "q_a", "q_b",
                         "q_norm"]
  assert mla["q_b"]["kernel"].shape == (24, 4, 32)
  assert mla["kvb"].shape == (32, 4, 32)
  assert sorted(toy["params"]["layer_1"]["moe"]) == [
      "down", "gate", "router", "router_bias", "shared", "up"]


def test_yarn_at_the_published_numbers():
  """``low`` 10, ``high`` 23 and ``m^2`` 1.87385 by hand; the program's
  frequencies are the reference's; fast dims keep their frequency, slow dims
  are divided by 40 and the ramp lies strictly between."""
  z = fam.sizes(PUBLISHED)

  def turns_at(n):
    return 64 * math.log(4096 / (2 * math.pi * n)) / (2 * math.log(10000))

  assert (math.floor(turns_at(32)), math.ceil(turns_at(1))) == (10, 23)
  want = [10000 ** (-i / 32) * ((1 - min(max((i - 10) / 13, 0), 1))
                                + min(max((i - 10) / 13, 0), 1) / 40)
          for i in range(32)]
  got = tfm.yarn_frequencies(10000.0, 64, 40.0, 4096, 32.0, 1.0)
  np.testing.assert_allclose(got, want, rtol=1e-12)
  np.testing.assert_allclose(fam.yarn_frequencies(z), want, rtol=1e-12)
  assert math.isclose(got[10], 10000 ** (-10 / 32), rel_tol=1e-12) \
      and math.isclose(got[23], 10000 ** (-23 / 32) / 40, rel_tol=1e-12)
  assert all(10000 ** (-i / 32) / 40 < got[i] < 10000 ** (-i / 32)
             for i in range(11, 23))
  cfg = fam.program_config(PUBLISHED, 16384)
  m2 = tfm.yarn_softmax_factor(cfg)
  assert abs(m2 - 1.87385) < 1e-5 and abs(m2 - (0.1 * math.log(40) + 1) ** 2) \
      < 1e-12
  assert abs(fam.softmax_scale(z) - 0.135234) < 1e-6
  assert fam.softmax_scale(z, "no_mscale") == 192 ** -0.5
  assert tfm.yarn_softmax_factor(tfm.TransformerConfig()) == 1.0
  # the toy's ramp takes values strictly between 0 and 1
  toy_f = fam.yarn_frequencies(fam.sizes(TOY))
  plain = [10000 ** (-i / 8) for i in range(8)]
  assert toy_f[0] == plain[0] \
      and math.isclose(toy_f[3], plain[3] / 40, rel_tol=1e-12)
  assert plain[1] / 40 < toy_f[1] < plain[1]


# -- the layers and the whole model against the reference ---------------------


@pytest.mark.parametrize("layer", [0, 1], ids=["dense", "experts"])
def test_a_layer_equals_the_references_layer(toy, layer):
  """One ``Block`` of each kind over a random stream longer than YaRN's
  original length, with norm scales (the two inside the attention too) that
  are NOT all ones."""
  cfg, config = toy["cfg"], toy["config"]
  names = ("ln1", "ln2", "q_norm", "kv_norm")
  keys = jax.random.split(jax.random.PRNGKey(3), len(names) + 1)
  weights = dict(toy["weights"], **{
      n: 1.0 + 0.3 * jax.random.normal(k, toy["weights"][n].shape)
      for n, k in zip(names, keys)})
  tree = fam._to_program_tree(weights, fam.sizes(config))["layer_%d" % layer]
  x = jax.random.normal(keys[-1], (1, 96, 64))
  positions = jnp.arange(96)[None]
  block = tfm.Block(cfg, None, False, "mla", cfg.ffn_types[layer])
  got = block.apply({"params": tree}, x, positions)
  want = fam.reference_layer(weights, x, config, layer)
  np.testing.assert_allclose(got, want, atol=F32_ATOL)


def test_full_forward_equals_the_reference(toy):
  toks = _tokens(1, 1, 96)
  got = tfm.Transformer(toy["cfg"]).apply({"params": toy["params"]}, toks)
  want = fam.reference_logits(toy["weights"], toks, toy["config"])
  np.testing.assert_allclose(got, want, atol=F32_ATOL)


def test_the_full_forward_trains(toy):
  """Nothing refuses a gradient through the rotated latent layer's plain
  forward (no kernel lies on it): finite, and not zero in the new leaves."""
  toks = _tokens(2, 1, 24)
  model = tfm.Transformer(toy["cfg"])
  grads = jax.jit(jax.grad(lambda p: tfm.causal_lm_loss(
      model.apply({"params": p}, toks), toks)))(toy["params"])
  for name in ("q_a", "q_b", "kva"):
    g = grads["layer_1"]["mla"][name]["kernel"]
    assert bool(jnp.all(jnp.isfinite(g))) and float(jnp.abs(g).max()) > 0


def test_prefill_then_decode_through_the_slab_equals_the_reference(toy):
  """Three prompts: 16 tokens (ONE chunk), 11 (a PADDED chunk of 16) and 37
  (chunks of 16, 16 and a padded 8: the later ones at a cursor above 0),
  inserted and decoded by ``step_many`` at horizon 4 for 12 tokens, the slots
  at different cursors: the absorbed read of each slot's latent rows. Every
  served token is the reference's own first choice at its position (keys and
  values EXPANDED a head, a full forward), the chunked prompt's tokens equal
  its own ``greedy_generate_kv`` decode, and the counters sum over the live
  lanes."""
  cfg, params = toy["cfg"], toy["params"]
  dec = SlotDecoder(cfg, 3)
  assert dec.padded_prefill and dec.counted
  buckets = (16, 8)
  assert dec.plan(37, buckets=buckets) == [(16, 16), (16, 16), (8, 5)]
  prompts = [_tokens(20, 16), _tokens(21, 11), _tokens(22, 37)]
  budget = 13                                    # 1 + three horizons of 4
  slabs = dec.init_slabs()
  leaves = [x for x in jax.tree.leaves(slabs) if x.ndim == 3]
  assert [x.shape for x in leaves] == [(3, MAX_SEQ, 128)] * 3
  last, got = np.zeros(3, np.int32), [[], [], []]
  for slot, p in enumerate(prompts):
    row, first = dec.prefill(params, p, buckets=buckets)
    slabs = dec.insert(slabs, row, slot)
    last[slot] = first
    got[slot].append(first)
  left = np.full(3, budget - 1, np.int32)
  totals = dict(context=0, held=0, touched=0, group=0)
  for _ in range(3):
    slabs, toks, _, _, counts = dec.step_many(params, slabs, last, left > 0,
                                              left, 4)
    toks = np.asarray(toks)
    assert sorted(counts) == sorted(totals)
    for name in totals:
      totals[name] += int(counts[name])
    for slot in range(3):
      got[slot].extend(toks[:, slot])
    last, left = toks[-1], left - 4
  # one latent leaf a layer: 3 writes and 3 reads a step, none by a kernel on
  # the CPU, none over a ring
  assert dec.cursor_writes[4] == (3 * 4, 0)
  assert dec.attn_reads[4] == (3 * 4, 0, 0)
  steps = 3 * (budget - 1)
  assert totals["context"] == sum(len(p) + j for p in prompts
                                  for j in range(budget - 1))
  # a token that kept the held experts' group may choose some of them, one
  # that did not can choose none: 2 expert layers
  assert 0 < totals["group"] < 2 * steps
  assert 0 < totals["touched"] <= totals["held"] <= 4 * totals["group"]
  # the chunked prompt's tokens are its own plain cached decode's
  want = np.asarray(tfm.greedy_generate_kv(
      params, cfg, jnp.asarray(prompts[2])[None], budget))[0, 37:]
  np.testing.assert_array_equal(np.asarray(got[2]), want)
  # ONE reference forward over the three rows (the shorter ones padded with
  # token 0 behind their end, which a causal model's earlier positions do not
  # see)
  seqs = np.zeros((3, 37 + budget), np.int32)
  for slot, p in enumerate(prompts):
    seqs[slot, :len(p) + budget] = np.concatenate([p, got[slot]])
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
      np.testing.assert_array_equal(
          x, [len(p) + budget - 1 for p in prompts])


def test_chunked_cached_decode_equals_the_full_forward_at_every_position(toy):
  """Prefill in chunks, then single tokens, through the scalar-cursor cache:
  the logits at EVERY position are the reference's full forward's (a chunk of
  16 tokens x 4 heads is absorbed, one of 64 x 4 takes the wide branch)."""
  cfg, params = toy["cfg"], toy["params"]
  model = tfm.Transformer(cfg)
  toks = _tokens(30, 1, 96)
  cache = tfm._zero_cache(model, 1)
  step = jax.jit(lambda c, t: model.apply(
      {"params": params, "cache": c}, t, decode=True, mutable=["cache"]))
  outs, off = [], 0
  for seg in (64, 16, 8, 4, 1, 1, 1, 1):
    logits, mut = step(cache, toks[:, off:off + seg])
    cache, off = mut["cache"], off + seg
    outs.append(logits)
  want = fam.reference_logits(toy["weights"], toks, toy["config"])
  np.testing.assert_allclose(jnp.concatenate(outs, axis=1), want,
                             atol=F32_ATOL)


# -- the kernels' paths, in interpret mode ------------------------------------


def _mla_step(cfg, params, cache, x):
  return mla_mod.MLA(cfg).apply({"params": params, "cache": cache}, x,
                                decode=True, mutable=["cache"])


def test_the_absorbed_read_by_the_kernel_equals_the_dense_and_the_expanded(
    monkeypatch):
  """One latent layer in bf16 over a slab of 256 rows with slots at cursors 0,
  5, 127, 128, 129 and 200: ``ops.decode_attention`` handed the leaf as K and
  as V (interpret mode; rows past the cursor never
  attended: they hold huge numbers) gives the dense absorbed contraction's
  output, and both give the EXPANDED attention (keys and values a head from
  the same cache rows, a plain softmax) computed here in float32."""
  toy = _toy(max_seq=256)
  cfg = dataclasses.replace(toy["cfg"], dtype=jnp.bfloat16)
  params = jax.tree.map(lambda w: w.astype(jnp.bfloat16)
                        if w.ndim > 1 else w, toy["params"]["layer_1"]["mla"])
  cursors = (0, 5, 127, 128, 129, 200)
  b = len(cursors)
  keys = jax.random.split(jax.random.PRNGKey(5), 2)
  rows = jnp.arange(256)[None, :, None]
  lanes = jnp.arange(128)[None, None, :]
  leaf = jax.random.normal(keys[0], (b, 256, 128))
  leaf = jnp.where(lanes < 48, leaf, 0.0)                 # the padding is zeros
  leaf = jnp.where(rows < jnp.asarray(cursors)[:, None, None], leaf,
                   80.0).astype(jnp.bfloat16)
  cache = {"cached_kv": leaf, "index": jnp.asarray(cursors, jnp.int32)}
  x = jax.random.normal(keys[1], (b, 1, 64)).astype(jnp.bfloat16)
  with tfm.decode_attention_tally() as reads:
    dense, mut = _mla_step(cfg, params, cache, x)
  assert reads == {"reads": 1, "ragged": 0, "ring": 0}
  monkeypatch.setenv("TOS_PALLAS_INTERPRET", "0")
  monkeypatch.setattr(ops, "pallas_interpret", lambda: True)
  with tfm.decode_attention_tally() as reads:
    kernel, mut_k = _mla_step(cfg, params, cache, x)
  assert reads == {"reads": 1, "ragged": 1, "ring": 0}
  np.testing.assert_array_equal(mut["cache"]["cached_kv"],
                                mut_k["cache"]["cached_kv"])
  np.testing.assert_array_equal(mut_k["cache"]["index"],
                                np.asarray(cursors) + 1)
  # float32 activations keep the dense read (the kernel takes a bf16 query)
  with tfm.decode_attention_tally() as reads:
    _mla_step(dataclasses.replace(cfg, act_f32=True), params, cache, x)
  assert reads == {"reads": 1, "ragged": 0, "ring": 0}
  np.testing.assert_allclose(np.asarray(kernel, np.float32),
                             np.asarray(dense, np.float32), atol=3e-2)
  # the expanded attention, slot by slot, from the rows the step left
  f32 = jax.tree.map(lambda w: w.astype(jnp.float32), params)
  new = mut["cache"]["cached_kv"].astype(jnp.float32)
  freqs = tfm.yarn_frequencies(1e4, 16, 40.0, 64)
  scale = 32 ** -0.5 * tfm.yarn_softmax_factor(cfg)
  xf = x.astype(jnp.float32)
  cq = xf @ f32["q_a"]["kernel"]
  cq = cq / jnp.sqrt(jnp.mean(cq * cq, -1, keepdims=True) + cfg.norm_eps) \
      * f32["q_norm"]["scale"]
  q = jnp.einsum("bsr,rhk->bshk", cq, f32["q_b"]["kernel"])
  for i, c in enumerate(cursors):
    lat = new[i]              # [256, 128]: rows 0..c count, the rest masked
    kv = jnp.einsum("tr,rhk->thk", lat[:, :32], f32["kvb"])
    k = jnp.concatenate([kv[..., :16], jnp.broadcast_to(
        lat[:, None, 32:48], (256, 4, 16))], -1)
    qi = jnp.concatenate([q[i, 0, :, :16], fam._rotate(
        q[i, :, :, 16:], jnp.asarray([c]), freqs)[0]], -1)
    p = jax.nn.softmax(jnp.where(
        jnp.arange(256) <= c, jnp.einsum("hk,thk->ht", qi, k) * scale,
        -jnp.inf), -1)
    o = jnp.einsum("ht,thk->hk", p, kv[..., 16:])
    want = jnp.einsum("hk,hkd->d", o, f32["out"]["kernel"])
    np.testing.assert_allclose(np.asarray(kernel[i, 0], np.float32), want,
                               atol=6e-2)


def test_decode_kernel_over_a_leaf_that_is_keys_and_values():
  """``ops.decode_attention`` handed ONE leaf as K and as V (a latent cache):
  the softmax over each slot's rows below its cursor and its own row, the
  output the probabilities' sum of the same rows, to float32 (three exact
  terms); ``scale`` is the scores' scale and not the leaf's width's; the
  published shape fits the kernel's VMEM budget at 32 slots and not at 48."""
  b, h, c = 3, 8, 128
  keys = jax.random.split(jax.random.PRNGKey(6), 3)
  q = jax.random.normal(keys[0], (b, h, c)).astype(jnp.bfloat16)
  own = jax.random.normal(keys[1], (b, 1, c)).astype(jnp.bfloat16)
  leaf = jax.random.normal(keys[2], (b, 384, c)).astype(jnp.bfloat16)
  cursor = jnp.asarray([0, 130, 384], jnp.int32)
  got = ops.decode_attention(q, own, own, leaf, leaf, cursor, interpret=True,
                             scale=0.05)
  for i, n in enumerate([0, 130, 384]):
    rows = jnp.concatenate([leaf[i, :n], own[i]]).astype(jnp.float32)
    p = jax.nn.softmax(jnp.einsum(
        "hc,tc->ht", q[i].astype(jnp.float32), rows, precision=_HI) * 0.05, -1)
    np.testing.assert_allclose(
        got[i], jnp.einsum("ht,tc->hc", p, rows, precision=_HI), atol=2e-5)
  other = ops.decode_attention(q, own, own, leaf, leaf, cursor,
                               interpret=True)              # 128^-0.5
  assert float(jnp.abs(other - got).max()) > 0.05
  sup = ops.decode_attention_supports
  bf = jnp.bfloat16
  assert sup((32, 128, 640), bf, (32, 16384, 640), bf)
  assert sup((24, 128, 640), bf, (24, 16384, 640), bf)
  assert not sup((48, 128, 640), bf, (48, 16384, 640), bf)


def test_prefill_chunks_go_through_the_flash_forward(monkeypatch):
  """A row cache of several ``_ROW_BLOCK``s (32 rows here), attention forced
  to the kernels (interpret mode): chunks of 48 tokens x 4 heads are WIDE (over
  128 rows of the MXU); the FIRST attends itself through the flash forward at
  keys of 32 / values of 16 and the scale with ``m^2``, the later ones attend
  the row through ``flash_attention_block`` block by block, the latent
  expanded a block at a time; the logits at every position are the
  reference's full forward's and the dense wide branch is never run."""
  monkeypatch.setattr(tfm, "_ROW_BLOCK", 32)
  toy = _toy()
  cfg = dataclasses.replace(toy["cfg"], attention_impl="flash")
  model = tfm.Transformer(cfg)
  toks = _tokens(31, 1, 96)
  cache = tfm._zero_cache(model, 1)
  wide_calls, scales = [], []
  real_wide = mla_mod.MLA._wide
  monkeypatch.setattr(
      mla_mod.MLA, "_wide",
      lambda self, *a: wide_calls.append(1) or real_wide(self, *a))
  real_block = ops.flash_attention_block
  monkeypatch.setattr(
      ops, "flash_attention_block",
      lambda *a, **kw: scales.append(kw["scale"]) or real_block(*a, **kw))
  # one program for both chunks (the cursor is traced: a cond picks)
  step = jax.jit(lambda c, t: model.apply(
      {"params": toy["params"], "cache": c}, t, decode=True,
      mutable=["cache"]))
  outs = []
  for off in range(0, 96, 48):
    logits, mut = step(cache, toks[:, off:off + 48])
    cache = mut["cache"]
    outs.append(logits)
  assert not wide_calls and scales
  assert all(abs(s - 32 ** -0.5 * 1.87385) < 1e-5 for s in scales)
  want = fam.reference_logits(toy["weights"], toks, toy["config"])
  np.testing.assert_allclose(jnp.concatenate(outs, axis=1), want,
                             atol=F32_ATOL)
  # float32 activations keep the dense wide branch, whatever is forced
  wide_calls.clear()
  model32 = tfm.Transformer(dataclasses.replace(cfg, act_f32=True))
  model32.apply({"params": toy["params"], "cache": tfm._zero_cache(model32, 1)},
                toks[:, :48], decode=True, mutable=["cache"])
  assert wide_calls


# -- the router's group limit -------------------------------------------------


def _brute_route(s, bias, groups, kept, k, scale):
  """The group-limited selection, one token at a time, in numpy."""
  per = len(s) // groups
  c = s + bias
  score = [np.sort(c[g * per:(g + 1) * per])[-2:].sum() for g in range(groups)]
  stay = np.argsort(score)[-kept:]
  masked = np.full_like(c, -np.inf)
  for g in stay:
    masked[g * per:(g + 1) * per] = c[g * per:(g + 1) * per]
  chosen = np.argsort(masked)[-k:]
  return set(chosen), {int(e): s[e] / s[chosen].sum() * scale for e in chosen}, \
      set(int(g) for g in stay)


def test_the_group_limit_equals_a_brute_force_selection():
  """256 experts in 8 groups of 32, 4 groups and 8 experts a token (the
  published numbers) over 64 tokens: experts, weights and kept groups are a
  token-by-token numpy selection's; for most tokens the UNLIMITED top 8 would
  cross into a fifth group, the limited never leaves its four; one token is
  built so that its three best experts lie in a group whose two-best sum
  loses."""
  e, groups, kept, k = 256, 8, 4, 8
  keys = jax.random.split(jax.random.PRNGKey(11), 2)
  x = jax.random.normal(keys[0], (64, e))
  # token 0: group 7 has the single best expert and nothing else; its
  # two-best sum loses to four groups of two good experts each
  row = jnp.full((e,), -4.0).at[7 * 32].set(6.0)
  for g in range(4):
    row = row.at[g * 32].set(2.0).at[g * 32 + 1].set(2.0)
  x = x.at[0].set(row)
  bias = 0.02 * jax.random.normal(keys[1], (e,))
  experts, weights, groups_kept = ep.route_sigmoid_topk(
      x, jnp.eye(e), bias, k, 2.5, groups, kept)
  free, _ = ep.route_sigmoid_topk(x, jnp.eye(e), bias, k, 2.5)
  s = np.asarray(jax.nn.sigmoid(x), np.float64)
  crossed = 0
  for t in range(64):
    chosen, w, stay = _brute_route(s[t], np.asarray(bias, np.float64), groups,
                                   kept, k, 2.5)
    assert set(np.asarray(experts[t]).tolist()) == chosen
    assert set(np.flatnonzero(np.asarray(groups_kept[t])).tolist()) == stay
    for j in range(k):
      assert abs(float(weights[t, j]) - w[int(experts[t, j])]) < 1e-5
    assert {int(x) // 32 for x in np.asarray(experts[t])} <= stay
    crossed += len({int(x) // 32 for x in np.asarray(free[t])}) > kept
  assert crossed > 32
  assert 7 * 32 in np.asarray(free[0]) and 7 * 32 not in np.asarray(experts[0])
  np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-5)
  # the defaults are the unlimited router, with two members
  assert len(ep.route_sigmoid_topk(x, jnp.eye(e), bias, k, 2.5)) == 2


def test_the_sixteen_shares_add_up_to_the_uncut_layer(toy):
  """The sizing guide's share test: 32 experts in 4 groups of 8 over 16 chips,
  2 each, so a group lies on four chips. The routed parts the 16 shares
  compute plus the shared expert COUNTED ONCE equal the uncut reference layer
  (all 32 held); every token's 4 assignments are computed by exactly one share
  each, all of them inside its 2 kept groups."""
  uncut = dict(toy["config"], n_routed_experts=32)
  z_all = fam.sizes(uncut)
  w_all = fam._layer_weights(fam.make_weights(7, uncut), z_all, 1)
  # the layer's own [held, ...] stacks out of the model's [layers, held, ...]
  w_all.update({n: w_all[n][w_all["exp_at"]]
                for n in ("exp_gate", "exp_up", "exp_down")})
  x = jax.random.normal(jax.random.PRNGKey(8), (2, 24, 64))
  want = fam._experts(x, w_all, z_all, "f32")
  shared = fam._swiglu(x, w_all["shared_gate"], w_all["shared_up"],
                       w_all["shared_down"], "f32")
  flat = x.reshape(-1, 64)
  experts, weights, kept = ep.route_sigmoid_topk(
      flat, w_all["router"], w_all["router_bias"], z_all["top_k"], 2.5, 4, 2)
  assert bool(jnp.all(jnp.take_along_axis(kept, experts // 8, axis=1)))
  total, assigned = shared, 0
  for share in range(16):
    part = slice(2 * share, 2 * share + 2)
    y, held = ep.held_experts_ffn(
        flat, experts, weights, w_all["exp_gate"][part], w_all["exp_up"][part],
        w_all["exp_down"][part], 2 * share)
    total, assigned = total + y.reshape(x.shape), assigned + int(held.sum())
    # and the reference, given the same share, agrees with the program's part
    z = dict(z_all, held=2, first=2 * share)
    w = dict(w_all, **{n: w_all[n][part]
                       for n in ("exp_gate", "exp_up", "exp_down")})
    np.testing.assert_allclose(y.reshape(x.shape) + shared,
                               fam._experts(x, w, z, "f32"), atol=3e-5)
  np.testing.assert_allclose(total, want, atol=3e-5, rtol=3e-5)
  assert assigned == 2 * 24 * z_all["top_k"]
  # the layer's module is what computes a share in the program; its group
  # counter says which tokens kept group 0, where experts 0-3 lie
  layer = toy["params"]["layer_1"]["moe"]
  got, sown = experts_mod.HeldExperts(toy["cfg"]).apply(
      {"params": layer}, x, mutable=["counters"])
  z_toy = fam.sizes(toy["config"])
  np.testing.assert_allclose(
      got, fam._experts(x, fam._layer_weights(toy["weights"], z_toy, 1),
                        z_toy, "f32"), atol=3e-5)
  (group,), (held,) = sown["counters"]["group"], sown["counters"]["held"]
  np.testing.assert_array_equal(group, np.asarray(kept[:, 0]))
  assert bool(jnp.all((held > 0) <= group)) and 0 < int(group.sum()) < 48


# -- the fields' defaults are today's programs --------------------------------


#: sha256 (16 hex) of the dead-code-eliminated jaxpr of the serving programs
#: of the three sparse models at their configuration files' rehearsal sizes, as
#: commit 1e86791 traced them (``_fingerprints`` run there)
TODAYS_PROGRAMS = {
    "kimi.prefill16": "dae285d66d921405",
    "kimi.step_many": "893a0f7e68e3e8e7",
    "mimo.prefill16": "61f5844935530efc",
    "mimo.step_many": "3f4f5ac619e96728",
    "trinity.prefill16": "cc3dab90807b2faf",
    "trinity.step_many": "4891cc6f59f11c58",
}


def _fingerprint(fn, *args):
  from jax._src.interpreters import partial_eval as pe
  closed = jax.make_jaxpr(fn)(*args)
  jaxpr, _ = pe.dce_jaxpr(closed.jaxpr, [True] * len(closed.jaxpr.outvars))
  return hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]


def _fingerprints(name, cfg):
  """The decode step (2 slots, horizon 2) and one padded prefill chunk of 16
  tokens of ``cfg``'s serving programs, traced abstractly."""
  dec = SlotDecoder(cfg, 2)
  params = jax.eval_shape(lambda: tfm.Transformer(cfg).init(
      jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
  row = jax.eval_shape(lambda: tfm._zero_cache(dec.model, 1))
  i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)       # noqa: E731
  return {
      name + ".step_many": _fingerprint(
          dec.step_many_jit(2), params, jax.eval_shape(dec.init_slabs), i32(2),
          jax.ShapeDtypeStruct((2,), jnp.bool_), i32(2)),
      name + ".prefill16": _fingerprint(dec._prefill_impl, params, row,
                                        i32(1, 16), i32())}


@pytest.mark.parametrize("model,config,family", [
    ("kimi", "kimi-linear-48b-a3b", "kimi_linear_family"),
    ("trinity", "trinity-large-preview", "trinity_family"),
    ("mimo", "mimo-v2-flash", "mimo_v2_flash_family"),
])
def test_the_new_fields_at_their_defaults_are_todays_programs(
    model, config, family):
  """Kimi's (latent layers without a query rank or rotation, float32
  activations), Trinity's and MiMo's (held experts without a group limit)
  decode step and prefill chunk trace to the programs they were before the
  fields existed."""
  import importlib
  other = importlib.import_module(family)
  cfg = other.program_config(_rehearsal(config), 128, dtype=jnp.bfloat16)
  got = _fingerprints(model, cfg)
  assert got == {k: v for k, v in TODAYS_PROGRAMS.items()
                 if k.startswith(model + ".")}


# -- refusals, by name --------------------------------------------------------


def test_config_checks_the_new_fields(toy):
  cfg = toy["cfg"]
  plain = dict(vocab_size=97, num_layers=2, num_heads=4, d_model=32, d_ff=64,
               max_seq_len=48)
  for changes, reason in [
      (dict(rope_yarn_original=0), "rope_yarn_original >= 1"),
      (dict(rope_yarn_factor=0.5), "a factor >= 1"),
      (dict(rope_yarn_beta_fast=1.0), "rope_yarn_beta_fast > rope_yarn_beta"),
      (dict(mla_rope=False), "ROTATED PART OF LATENT LAYERS"),
      (dict(mla_rope_dim=15), "whole pairs"),
      (dict(experts_groups=5), "must divide the router's 32 experts"),
      (dict(experts_groups_kept=0), "0 < experts_groups_kept=0"),
      (dict(experts_groups_kept=5), "0 < experts_groups_kept=5"),
      (dict(experts_groups=32, experts_groups_kept=32), "groups of at least 2"),
      (dict(experts_groups=16, experts_groups_kept=1), "room for"),
      (dict(kv_cache_dtype="int8"), "an int8 latent"),
      (dict(kv_page_size=16, kv_num_pages=8, kv_pages_per_slot=6),
       "a paged latent cache"),
  ]:
    with pytest.raises(ValueError, match=reason):
      dataclasses.replace(cfg, **changes)
  # an attention layer's rotary at scaled frequencies is not built
  with pytest.raises(ValueError, match="an attention layer's rotary") as err:
    tfm.TransformerConfig(**plain, rope_yarn_factor=40.0,
                          rope_yarn_original=64)
  assert "rope_yarn_factor" in str(err.value)
  with pytest.raises(ValueError, match="an attention layer's rotary"):
    tfm.TransformerConfig(**plain, layer_types=("mla", "attn"), mla_rope=True,
                          rope_yarn_factor=40.0, rope_yarn_original=64)


def test_a_mesh_refuses_such_a_latent_layer(toy):
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib
  mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=-1, tensor=2))
  with pytest.raises(ValueError, match="a sharding of the rank") as err:
    tfm.Transformer(dataclasses.replace(toy["cfg"], vocab_size=256),
                    mesh=mesh).init(
        jax.random.PRNGKey(0), jnp.zeros((4, 16), jnp.int32))
  assert "mla_q_rank" in str(err.value)


@pytest.mark.parametrize("kwargs,reason", [
    (dict(page_size=16), "a paged latent cache"),
    (dict(prefix_pages=4), "mla layers cache no K/V pages"),
    (dict(spec_depth=40), "a verify window over a latent leaf"),
])
def test_engine_refuses_what_a_latent_leaf_cannot_take(toy, kwargs, reason):
  """Pages, a prefix cache and a verify window wider than the absorbed read
  are refused for this model: nothing stands in for the
  multi-token-prediction block's speculation."""
  with pytest.raises(ValueError, match=reason):
    serving.ServingEngine(toy["params"], toy["cfg"], num_slots=2, **kwargs)


def test_the_multi_token_prediction_block_is_refused_unless_left_out():
  config = {k: v for k, v in TOY.items() if k != "multi_token_prediction"}
  with pytest.raises(ValueError, match="multi-token-prediction block") as err:
    fam.sizes(config)
  assert "num_nextn_predict_layers=1" in str(err.value)
  assert fam.sizes(dict(config, num_nextn_predict_layers=0))["layers"] == 3
  with pytest.raises(ValueError, match="mscale == mscale_all_dim"):
    fam.sizes(dict(TOY, rope_scaling=dict(TOY["rope_scaling"], mscale=0.7)))


# -- the engine ---------------------------------------------------------------


def test_engine_serves_and_counts(toy):
  """``ServingEngine`` on 2 slots: every request's tokens are its own
  ``greedy_generate_kv`` decode, and the stats carry the step's sums (3 latent
  writes and 3 reads a step, the group counter beside ``held``)."""
  cfg, params = toy["cfg"], toy["params"]
  eng = serving.ServingEngine(params, cfg, num_slots=2, max_restarts=0,
                              buckets=(16, 8)).start()
  try:
    prompts = [_tokens(40 + i, n) for i, n in enumerate((5, 37, 37, 50))]
    rids = [eng.submit(p, max_new_tokens=13) for p in prompts]
    outs = [eng.result(r, timeout=300) for r in rids]
    stats = dict(eng.stats)
  finally:
    eng.stop()
  for p, out in zip(prompts, outs):
    want = np.asarray(tfm.greedy_generate_kv(
        params, cfg, jnp.asarray(p)[None], 13))[0]
    np.testing.assert_array_equal(np.asarray(out), want)
  assert 0 < stats["moe_group_hits"] < 2 * stats["live_slot_steps"]
  assert 0 < stats["moe_assignments_held"] <= 4 * stats["moe_group_hits"]
  assert stats["cursor_leaf_writes"] == 3 * stats["steps"]
  assert stats["decode_attn_reads"] == 3 * stats["steps"]
  assert stats["decode_attn_reads_ragged"] == 0          # the CPU
  assert stats["engine_restarts"] == 0 and stats["replay_mismatches"] == 0


# -- the controls of the comparison that decides ``correct`` ------------------


@pytest.mark.parametrize("control", ["fp8", "no_group", "no_mscale"])
def test_a_control_fails_the_comparison_at_toy_width(toy, control):
  """What the benchmark's check computes (how far a served token's float32
  reference logit lies below the reference's best), on the program's greedy
  tokens and on a control's: the fp8 reference's first choices, those of the
  reference WITHOUT the router's group limit and those of the reference
  WITHOUT ``m^2`` on its softmax scale. The program reads zero to rounding;
  each control lies far beyond the rehearsal's limits (mean 0.002, max
  0.02)."""
  p = _tokens(50, 24)
  out = np.asarray(tfm.greedy_generate_kv(
      toy["params"], toy["cfg"], jnp.asarray(p)[None], 72))
  z = fam.reference_logits(toy["weights"], out, toy["config"])[0]
  best = z[:-1].max(axis=-1)
  served = jnp.take_along_axis(z[:-1], jnp.asarray(out)[0, 1:, None], 1)[:, 0]
  sound = (best - served)[len(p) - 1:]
  low = fam.reference_logits(toy["weights"], out, toy["config"], control)[0]
  picked = jnp.take_along_axis(z[:-1], jnp.argmax(low[:-1], -1)[:, None],
                               1)[:, 0]
  gaps = (best - picked)[len(p) - 1:]
  assert float(sound.max()) < 1e-3
  assert float(gaps.max()) > 0.2 and float(gaps.mean()) > 0.02, (
      control, float(gaps.max()), float(gaps.mean()))
