"""Attention over the cached tokens a learned INDEXER chooses
(``TransformerConfig.sparse_topk`` / ``index_heads`` / ``index_head_dim``: a
third cache leaf a layer, a mask by query from an exact top-k of the index
scores, taken by every lowering of the attention) and a SOFTMAX router
(``experts_score``), through the plain forward, the cached decode and the
serving slab, against the plain reference in ``keye_vl2_family.py`` (a
byte-for-byte copy of ``benchmarks/families/keye_vl2.py``: float32, a full
forward with the selection written as a mask over the whole sequence, no
cache, none of the program's code). Seeded weights, toy widths, CPU.
"""

import dataclasses
import hashlib
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import keye_vl2_family as fam
from tensorflowonspark_tpu import ops, serving
from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.parallel import expert_parallel as ep
from tensorflowonspark_tpu.serving.slots import SlotDecoder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
flash_mod = importlib.import_module("tensorflowonspark_tpu.ops.flash_attention")


def _file(name):
  with open(os.path.join(REPO, "benchmarks", "configs", name + ".json")) as f:
    return json.load(f)


def _rehearsal(name):
  f = _file(name)
  return dict({k: v for k, v in f.items() if k != "rehearse"}, **f["rehearse"])


_FILE = _file("keye-vl-2.0-30b-a3b")
PUBLISHED = {k: v for k, v in _FILE.items() if k != "rehearse"}
#: the cell's rehearsal sizes: 3 layers, hidden 64, 4 / 2 heads of 16, 2 index
#: heads of 8, 8 chosen, 4 held of 16 experts with 4 a token
TOY = _rehearsal("keye-vl-2.0-30b-a3b")
MAX_SEQ = 96
TOPK = 8
VOCAB = TOY["vocab_size"]
#: float32 on both sides, the same mathematics and the SAME rows and experts
#: chosen: what is left is summation order
F32_ATOL = 2e-4

_WEIGHTS = {}


def _toy(max_seq=MAX_SEQ):
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
  assert cfg.ffn_types == ("experts",) * 3 and not cfg.layer_types
  assert (cfg.sparse_topk, cfg.index_heads, cfg.index_head_dim) == (8, 2, 8)
  assert (cfg.num_heads, cfg.kv_heads, cfg.head_dim) == (4, 2, 16)
  assert (cfg.experts_total, cfg.experts_held, cfg.experts_top_k) == (16, 4, 4)
  assert cfg.experts_score == "softmax" and cfg.experts_shared == 0
  assert cfg.qk_norm and not cfg.tie_embeddings
  z = fam.sizes(PUBLISHED)
  assert (z["heads"], z["kv_heads"], z["head_dim"], z["d_model"]) \
      == (32, 4, 128, 2048)
  assert (z["index_heads"], z["index_dim"], z["topk"]) == (16, 64, 2048)
  assert (z["routed"], z["held"], z["top_k"], z["expert_ff"]) \
      == (128, 16, 8, 768)
  assert (z["layers"], z["vocab"], z["sections"]) == (6, 19072, (16, 24, 24))
  assert fam.param_count(PUBLISHED) == _FILE["parameters_as_built"] \
      == 659517696
  attn = toy["params"]["layer_1"]["attn"]
  assert sorted(attn) == ["index_k", "index_k_norm", "index_q", "index_w",
                          "k", "k_norm", "out", "q", "q_norm", "v"]
  assert attn["index_q"]["kernel"].shape == (64, 2, 8)
  assert attn["index_k"]["kernel"].shape == (64, 8)
  assert sorted(attn["index_k_norm"]) == ["bias", "scale"]
  # a softmax router has no selection bias
  assert sorted(toy["params"]["layer_1"]["moe"]) == [
      "down", "gate", "router", "up"]
  # the program's own init makes the same tree
  from flax.core import meta
  init = jax.eval_shape(lambda: meta.unbox(tfm.Transformer(cfg).init(
      jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
  assert jax.tree.map(lambda x: x.shape, init) \
      == jax.tree.map(lambda x: x.shape, toy["params"])


# -- the selection ------------------------------------------------------------


def _top_k_mask(scores, valid, k):
  """``lax.top_k``'s own choice as a mask (its order: the earlier position
  first among equal scores), one row at a time in numpy."""
  out = np.zeros(scores.shape, bool)
  for r, (s, v) in enumerate(zip(np.asarray(scores), np.asarray(valid))):
    _, idx = jax.lax.top_k(jnp.where(v, s, -jnp.inf), min(k, len(s)))
    idx = np.asarray(idx)[:min(k, int(v.sum()))]
    out[r, idx] = True
  return out


@pytest.mark.parametrize("case", ["random", "tied", "tie_at_the_last_place",
                                  "few_candidates", "zeros_of_both_signs"])
def test_the_threshold_search_is_lax_top_k(case):
  """``select_topk`` (32 passes of compare-and-count over the float's bits)
  keeps exactly what ``lax.top_k`` would, ties in order of position, and a
  query with at most ``k`` candidates keeps them all; so does the
  reference's ``select``."""
  rng = np.random.default_rng(3)
  n, k = 64, 8
  scores = rng.normal(size=(6, n)).astype(np.float32)
  at = np.asarray([63, 40, 20, 9, 7, 3])
  if case == "tied":
    scores = np.round(scores * 2) / 2          # many equal scores
  elif case == "tie_at_the_last_place":
    # rows 0-1: the 8th and 9th largest are EQUAL: the earlier position stays
    for r in (0, 1):
      order = np.argsort(-scores[r, :at[r] + 1])
      scores[r, order[8]] = scores[r, order[7]]
  elif case == "few_candidates":
    at = np.asarray([7, 6, 3, 0, 8, 2])
  elif case == "zeros_of_both_signs":
    scores = np.where(rng.random((6, n)) < 0.7, 0.0, scores).astype(np.float32)
    scores[:, ::3] *= -1.0                     # -0.0 and 0.0 are one score
  valid = np.arange(n)[None, :] <= at[:, None]
  want = _top_k_mask(np.where(scores == 0, 0.0, scores), valid, k)
  got = tfm.select_topk(jnp.asarray(scores), jnp.asarray(valid), k)
  np.testing.assert_array_equal(np.asarray(got), want)
  assert (np.asarray(got).sum(-1) == np.minimum(at + 1, k)).all()
  ref = fam.select(jnp.asarray(scores)[None], jnp.asarray(at), k)[0]
  np.testing.assert_array_equal(np.asarray(ref), want)
  if case == "tie_at_the_last_place":
    order = np.argsort(-scores[0, :at[0] + 1], kind="stable")
    tied = sorted(np.flatnonzero(scores[0] == scores[0, order[7]]))
    assert want[0, tied[0]] and not want[0, tied[-1]]
  # jitted (the cond on ties is traced) it says the same
  jitted = jax.jit(lambda s, v: tfm.select_topk(s, v, k))(scores, valid)
  np.testing.assert_array_equal(np.asarray(jitted), want)


def test_index_scores_in_blocks_of_keys(monkeypatch):
  """A long row's index scores are made a block of keys at a time, up to the
  block that holds the last live position; the same numbers as one product,
  zeros past the live blocks."""
  rng = np.random.default_rng(5)
  iq = jnp.asarray(rng.normal(size=(1, 12, 2, 8)), jnp.float32)
  iw = jnp.asarray(rng.normal(size=(1, 12, 2)), jnp.float32)
  keys = jnp.asarray(rng.normal(size=(1, 96, 8)), jnp.float32)
  whole = tfm.index_scores(iq, iw, keys)
  want = fam.index_scores(iq, keys, iw)
  np.testing.assert_allclose(whole, want, atol=1e-5)
  monkeypatch.setattr(tfm, "_ROW_BLOCK", 32)
  monkeypatch.setattr(tfm, "_INDEX_SCORE_BYTES", 0)
  blocked = jax.jit(lambda live: tfm.index_scores(iq, iw, keys, live=live))(40)
  np.testing.assert_allclose(blocked[..., :64], whole[..., :64], atol=1e-6)
  assert not np.asarray(blocked[..., 64:]).any()
  np.testing.assert_allclose(tfm.index_scores(iq, iw, keys), whole, atol=1e-6)
  # a leaf of more lanes than the head (zeros behind): the same scores
  padded = jnp.pad(keys, ((0, 0), (0, 0), (0, 120)))
  np.testing.assert_allclose(tfm.index_scores(iq, iw, padded), whole,
                             atol=1e-6)


def test_the_programs_chosen_rows_are_selects(toy, monkeypatch):
  """Layer by layer the rows the program's full forward chooses are the rows
  ``select`` chooses over the reference's stream: the same mask, entry for
  entry (float32 on both sides)."""
  toks = _tokens(11, 2, 40)
  chosen = []
  real = tfm.select_topk
  monkeypatch.setattr(
      tfm, "select_topk",
      lambda *a: chosen.append(real(*a)) or chosen[-1])
  tfm.Transformer(toy["cfg"]).apply({"params": toy["params"]}, toks)
  streams = []
  fam.reference_logits(toy["weights"], toks, TOY, streams=streams)
  assert len(chosen) == len(streams) == 3
  for i, (got, x) in enumerate(zip(chosen, streams)):
    want = fam.chosen_rows(toy["weights"], x, TOY, i)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert (np.asarray(got).sum(-1)
            == np.minimum(np.arange(40) + 1, TOPK)).all()


# -- the forward passes against the reference ---------------------------------


def test_full_forward_equals_the_reference(toy):
  toks = _tokens(1, 2, 48)
  got = tfm.Transformer(toy["cfg"]).apply({"params": toy["params"]}, toks)
  want = fam.reference_logits(toy["weights"], toks, TOY)
  np.testing.assert_allclose(got, want, atol=F32_ATOL)


def test_the_selection_matters_at_toy_width(toy):
  """The reference WITHOUT the selection, with half of it, and with the
  router's weights not renormalised each differ from the program by far more
  than rounding: the comparison can see each piece."""
  toks = _tokens(2, 1, 48)
  got = tfm.Transformer(toy["cfg"]).apply({"params": toy["params"]}, toks)
  for control in ("no_select", "select_half", "no_renorm"):
    other = fam.reference_logits(toy["weights"], toks, TOY, control)
    # below 4 positions nothing is dropped even at half the selection
    assert float(jnp.max(jnp.abs(got - other)[:, 8:])) > 50 * F32_ATOL, control
  same = fam.reference_logits(toy["weights"], toks, TOY, "no_select")
  np.testing.assert_allclose(got[:, :TOPK], same[:, :TOPK], atol=F32_ATOL)


@pytest.mark.parametrize("buckets", [None, (4,), (16, 8)],
                         ids=["padded_plan", "chunks_of_4", "chunks_16_8"])
def test_prefill_then_decode_through_the_slab_equals_the_reference(
    toy, buckets):
  """Prompts of several lengths prefilled (one chunk, a padded chunk; chunks
  of 4, whose first two end at or below the 8 chosen and take the branch
  without an indexer while the later ones select at a cursor; chunks of 16 and
  8), inserted into a slab of three leaves a layer and decoded with every slot
  at its own cursor: each emitted token is the reference's greedy choice and
  its logit gap is 0."""
  cfg, params = toy["cfg"], toy["params"]
  dec = SlotDecoder(cfg, 4)
  assert dec.counted
  slabs = dec.init_slabs()
  leaves = slabs["layer_0"]["attn"]
  assert sorted(leaves) == ["cached_ik", "cached_k", "cached_v", "index"]
  assert leaves["cached_ik"].shape == (4, MAX_SEQ, tfm.INDEX_LANES)
  rng = np.random.default_rng(9)
  prompts = [rng.integers(0, VOCAB, n, dtype=np.int32) for n in (5, 23, 40, 16)]
  seqs, tok = [], []
  for slot, p in enumerate(prompts):
    row, first = dec.prefill(params, p, buckets=buckets)
    slabs = dec.insert(slabs, row, slot)
    seqs.append(list(p) + [first])
    tok.append(first)
  active, left = np.ones(4, bool), np.full(4, 50, np.int32)
  sums = {}
  for _ in range(3):
    slabs, toks, active, left, counts = dec.step_many(
        params, slabs, np.asarray(tok, np.int32), active, left, 4)
    toks = np.asarray(toks)
    for t in range(4):
      for s in range(4):
        seqs[s].append(int(toks[t, s]))
    tok = toks[-1]
    for k, v in counts.items():
      sums[k] = sums.get(k, 0) + int(v)
  for p, seq in zip(prompts, seqs):
    seq = np.asarray(seq, np.int32)
    z = fam.reference_logits(toy["weights"], seq[None], TOY)[0]
    gaps = jnp.max(z[:-1], -1) - jnp.take_along_axis(
        z[:-1], jnp.asarray(seq[1:, None]), -1)[:, 0]
    assert float(jnp.max(gaps[len(p) - 1:])) == 0.0
  # 12 steps x 4 lanes x 3 layers: a query keeps min(cursor + 1, 8) entries of
  # its cursor + 1 candidates; the prompt of 5 is limited from its 3rd step on
  cursors = [[len(p) + t for t in range(12)] for p in prompts]
  assert sums["sparse_kept"] == 3 * sum(min(c + 1, TOPK) for cs in cursors
                                        for c in cs)
  assert sums["sparse_candidates"] == 3 * sum(c + 1 for cs in cursors
                                              for c in cs)
  assert sums["sparse_limited"] == sum(c + 1 > TOPK for cs in cursors
                                       for c in cs)
  assert dec.sparse_reads[4] == 3 * 4 and dec.attn_reads[4] == (3 * 4, 0, 0)


def test_chunked_cached_decode_equals_the_full_forward_at_every_position(toy):
  """The shared-cursor cached path (``greedy_generate_kv``'s): a chunk of 24,
  then single tokens, every position's logits the full forward's."""
  cfg, params = toy["cfg"], toy["params"]
  model = tfm.Transformer(cfg)
  toks = _tokens(4, 2, 36)
  want = fam.reference_logits(toy["weights"], toks, TOY)
  cache = tfm._zero_cache(model, 2)
  step = jax.jit(lambda c, t: model.apply(
      {"params": params, "cache": c}, t, decode=True, mutable=["cache"]))
  outs = []
  for lo, hi in [(0, 24)] + [(i, i + 1) for i in range(24, 36)]:
    logits, mut = step(cache, toks[:, lo:hi])
    cache = mut["cache"]
    outs.append(logits)
  np.testing.assert_allclose(jnp.concatenate(outs, axis=1), want,
                             atol=F32_ATOL)
  out = tfm.greedy_generate_kv(params, cfg, toks[:, :20], 6)
  z = fam.reference_logits(toy["weights"], out, TOY)
  np.testing.assert_array_equal(np.asarray(out[:, 20:]),
                                np.asarray(jnp.argmax(z, -1)[:, 19:-1]))


def test_prefill_chunks_go_through_the_flash_kernels_under_the_keep_operand(
    monkeypatch):
  """A row cache of several ``_ROW_BLOCK``s (32 rows here), attention forced
  to the kernels (interpret mode): the FIRST chunk of 48 attends itself
  through the flash forward under the keep operand, the later one attends its
  row through ``flash_attention_block`` block by block, each with its columns
  of the mask; the logits at every position are the reference's."""
  monkeypatch.setattr(tfm, "_ROW_BLOCK", 32)
  toy = _toy()
  cfg = dataclasses.replace(toy["cfg"], attention_impl="flash")
  model = tfm.Transformer(cfg)
  toks = _tokens(31, 1, 96)
  kept = []
  real_block = flash_mod._flash_keep
  monkeypatch.setattr(
      flash_mod, "_flash_keep",
      lambda q, k, v, keep, *a: kept.append(keep.shape) or real_block(
          q, k, v, keep, *a))
  step = jax.jit(lambda c, t: model.apply(
      {"params": toy["params"], "cache": c}, t, decode=True,
      mutable=["cache"]))
  cache, outs = tfm._zero_cache(model, 1), []
  for off in range(0, 96, 48):
    logits, mut = step(cache, toks[:, off:off + 48])
    cache = mut["cache"]
    outs.append(logits)
  # a layer's fresh chunk [1, 48, 48] and a later chunk's blocks [1, 48, 32]
  assert (1, 48, 48) in kept and (1, 48, 32) in kept
  want = fam.reference_logits(toy["weights"], toks, TOY)
  np.testing.assert_allclose(jnp.concatenate(outs, axis=1), want,
                             atol=F32_ATOL)
  # the full forward through the flash forward under the operand
  got = jax.jit(lambda t: model.apply({"params": toy["params"]}, t))(toks)
  np.testing.assert_allclose(got, want, atol=F32_ATOL)


def test_a_long_rows_selection_runs_over_the_whole_row(monkeypatch):
  """A row of several ``_ROW_BLOCK``s (32 rows here, 128 positions): a chunk
  of 32 tokens at a cursor above the toy ``topk`` is scored a block of keys at
  a time up to the block that holds its last token (``index_scores(live=)``)
  and searched over the whole row, whatever the cursor (one width, one traced
  program); the logits are the reference's at every position."""
  monkeypatch.setattr(tfm, "_ROW_BLOCK", 32)
  toy = _toy(128)
  model = tfm.Transformer(toy["cfg"])
  toks = _tokens(41, 1, 128)
  widths, real = [], tfm.select_topk
  monkeypatch.setattr(
      tfm, "select_topk",
      lambda s, v, k: widths.append(s.shape[-1]) or real(s, v, k))
  step = jax.jit(lambda c, t: model.apply(
      {"params": toy["params"], "cache": c}, t, decode=True,
      mutable=["cache"]))
  cache, outs = tfm._zero_cache(model, 1), []
  for off in range(0, 128, 32):
    logits, mut = step(cache, toks[:, off:off + 32])
    cache = mut["cache"]
    outs.append(logits)
  assert set(widths) == {128} and len(widths) % 3 == 0, widths
  want = fam.reference_logits(toy["weights"], toks, TOY)
  np.testing.assert_allclose(jnp.concatenate(outs, axis=1), want,
                             atol=F32_ATOL)


# -- the kernels' keep operand -------------------------------------------------


def _dense_masked(q, k, v, keep):
  """``softmax(q k^T / sqrt(d))`` under ``keep [b, sq, sk]``, query head ``i``
  reading KV head ``i // g``, in float32."""
  g = q.shape[2] // k.shape[2]
  kk, vv = (jnp.repeat(x.astype(jnp.float32), g, axis=2) for x in (k, v))
  s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kk,
                 precision="highest") / q.shape[-1] ** 0.5
  p = jax.nn.softmax(jnp.where(keep[:, None], s, -1e30), axis=-1)
  return jnp.einsum("bhqk,bkhd->bqhd", p, vv, precision="highest")


def test_flash_forward_and_block_call_take_a_keep_operand():
  rng = np.random.default_rng(8)
  b, s, h, hk, d = 2, 64, 4, 2, 16
  q, k, v = (jnp.asarray(rng.normal(size=(b, s, n, d)), jnp.float32)
             for n in (h, hk, hk))
  at = np.arange(s)
  keep = tfm.select_topk(jnp.asarray(rng.normal(size=(b, s, s)), jnp.float32),
                         jnp.broadcast_to(at[None, :] <= at[:, None],
                                          (b, s, s)), 8)
  want = _dense_masked(q, k, v, keep)
  got = ops.flash_attention(q, k, v, causal=True, interpret=True, keep=keep,
                            blk_q=16, blk_k=16)
  np.testing.assert_allclose(got, want, atol=2e-5)
  # two blocks of keys merged, queries at a base: the later chunk's form
  qb, base = q[:, 32:], 32
  parts = [ops.flash_attention_block(
      qb, k[:, lo:lo + 32], v[:, lo:lo + 32], base, lo, causal=True,
      interpret=True, keep=keep[:, 32:, lo:lo + 32], blk_q=16, blk_k=16)
           for lo in (0, 32)]
  out, _ = flash_mod.merge_partials(*parts[0], *parts[1])
  np.testing.assert_allclose(out, want[:, 32:], atol=2e-5)
  # a gradient through the selection is refused by name
  with pytest.raises(ValueError, match="the flash backward takes no keep"):
    jax.grad(lambda x: ops.flash_attention(
        x, k, v, interpret=True, keep=keep).sum())(q)
  with pytest.raises(ValueError, match="keep operand beside a softmax scale"):
    ops.flash_attention_block(q, k, v, 0, 0, interpret=True, keep=keep,
                              scale=0.5)


@pytest.mark.parametrize("own_kept", [True, False])
def test_decode_kernel_with_keep_rows_equals_the_dense_branch(
    monkeypatch, own_kept):
  """``ops.decode_attention`` under keep rows (interpret mode) against the
  dense branch under the same mask: slots at cursors in different blocks, one
  at 0, the own token kept or dropped."""
  monkeypatch.setattr(ops, "pallas_interpret", lambda: True)
  rng = np.random.default_rng(12)
  b, mx, h, hk, d = 4, 512, 8, 2, 64
  bf = jnp.bfloat16
  q = jnp.asarray(rng.normal(size=(b, 1, h, d)), bf)
  k, v = (jnp.asarray(rng.normal(size=(b, 1, hk, d)), bf) for _ in range(2))
  ck, cv = (jnp.asarray(rng.normal(size=(b, mx, hk * d)), bf)
            for _ in range(2))
  lengths = jnp.asarray([0, 37, 300, mx - 1], jnp.int32)
  col = jnp.arange(mx)
  rows = tfm.select_topk(jnp.asarray(rng.normal(size=(b, mx)), jnp.float32),
                         col[None] < lengths[:, None], 24)
  # a slot with nothing cached keeps its own token: its one candidate
  own = jnp.asarray([True] + [own_kept] * 3)
  keep = (rows[:, None, :], own[:, None, None])
  assert ops.decode_attention_supports((b, h, d), bf, ck.shape, bf, cv.shape,
                                       keep=True)
  monkeypatch.setattr(ops, "pallas_kernels_enabled", lambda: False)
  dense = tfm._cached_attention(q, k, v, ck, cv, q_pos=lengths[:, None],
                                lengths=lengths, keep=keep)
  monkeypatch.setattr(ops, "pallas_kernels_enabled", lambda: True)
  with tfm.decode_attention_tally() as tally:
    got = tfm._cached_attention(q, k, v, ck, cv, q_pos=lengths[:, None],
                                lengths=lengths, keep=keep)
  assert tally == {"reads": 1, "ragged": 1, "ring": 0, "sparse": 1}
  np.testing.assert_allclose(np.asarray(got, np.float32),
                             np.asarray(dense, np.float32),
                             rtol=2e-2, atol=2e-2)
  # and it is not the read without the mask
  plain = tfm._cached_attention(q, k, v, ck, cv, q_pos=lengths[:, None],
                                lengths=lengths)
  assert float(jnp.max(jnp.abs(plain.astype(jnp.float32)
                               - got.astype(jnp.float32))[1:])) > 0.1
  with pytest.raises(ValueError, match="not beside a ring's skip or a sink"):
    ops.decode_attention(q[:, 0], k[:, 0], v[:, 0], ck, cv, lengths,
                         sink=jnp.zeros((h,)), keep=(rows, own),
                         interpret=True)


def test_the_keep_rows_count_in_the_kernels_vmem_budget():
  bf = jnp.bfloat16
  dec = importlib.import_module("tensorflowonspark_tpu.ops.decode_attention")
  base = dec._vmem_bytes(16, 32, 128, 512, 512)
  assert dec._vmem_bytes(16, 32, 128, 512, 512, 32768) \
      == base + 16 * 32768 * 4 < dec.VMEM_BUDGET
  # the cell's shape fits; a slab of 256 slots' keep rows would not
  assert ops.decode_attention_supports((16, 32, 128), bf, (16, 32768, 512),
                                       bf, keep=True)
  assert ops.decode_attention_supports((192, 32, 128), bf, (192, 32768, 512),
                                       bf)
  assert not ops.decode_attention_supports(
      (192, 32, 128), bf, (192, 32768, 512), bf, keep=True)


# -- the router ----------------------------------------------------------------


def test_the_softmax_router_equals_a_brute_force_top_k():
  rng = np.random.default_rng(21)
  x = rng.normal(size=(40, 32)).astype(np.float32)
  w = (rng.normal(size=(32, 16)) / 32 ** 0.5).astype(np.float32)
  experts, weights = ep.route_softmax_topk(jnp.asarray(x), jnp.asarray(w), 4)
  for t in range(40):
    z = x[t].astype(np.float64) @ w.astype(np.float64)
    p = np.exp(z - z.max())
    p /= p.sum()
    chosen = np.argsort(-p)[:4]
    assert set(np.asarray(experts[t])) == set(chosen)
    for e, g in zip(np.asarray(experts[t]), np.asarray(weights[t])):
      np.testing.assert_allclose(g, p[e] / p[chosen].sum(), rtol=1e-5)
  np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
  # the reference's route says the same, and its control does not renormalise
  wz = dict(router=jnp.asarray(w))
  z = dict(top_k=4)
  e2, w2 = fam.route(jnp.asarray(x)[None], wz, z)
  np.testing.assert_array_equal(np.asarray(e2[0]), np.asarray(experts))
  np.testing.assert_allclose(w2[0], weights, rtol=1e-5)
  _, raw = fam.route(jnp.asarray(x)[None], wz, z, renorm=False)
  assert float(jnp.max(raw.sum(-1))) < 0.9


def test_the_shares_add_up_to_the_uncut_layer(toy):
  """The guide's test of a cut by expert parallelism: the four shares of a
  toy layer (experts 0-3, 4-7, 8-11, 12-15, each with the whole router) add
  up to the reference's layer with all 16 experts held, once the attention
  branch (replicated on every chip) is counted once."""
  whole = dict(TOY, num_experts=16)
  w_all = fam.make_weights(5, whole)
  x = jnp.asarray(np.random.default_rng(6).normal(size=(1, 24, 64)),
                  jnp.float32)
  want = fam.reference_layer(w_all, x, whole, 1)
  z = fam.sizes(TOY)
  w1 = fam._layer_weights(w_all, fam.sizes(whole), 1)
  a = fam._rms_norm(x, w1["ln1"], z["eps"])
  with jax.default_matmul_precision("highest"):
    mid = x + fam._attention(a, w1, z, "f32")
  total = jnp.zeros_like(x)
  for first in range(0, 16, 4):
    share = dict(TOY, experts_first=first)
    w = {n: (v[:, first:first + 4] if n.startswith("exp_") else v)
         for n, v in w_all.items()}
    got = fam.reference_layer(w, x, share, 1)
    total = total + (got - mid)
    # the program's share is the reference's share
    cfg = fam.program_config(share, MAX_SEQ, dtype=jnp.float32)
    tree = fam._to_program_tree(w, fam.sizes(share))["layer_1"]
    block = tfm.Block(cfg, None, False, "attn", "experts")
    prog = block.apply({"params": tree}, x, jnp.arange(24)[None])
    np.testing.assert_allclose(prog, got, atol=F32_ATOL)
  np.testing.assert_allclose(mid + total, want, atol=F32_ATOL)


# -- positions of three components --------------------------------------------


def test_the_sectioned_rotation_of_equal_components_is_the_plain_one():
  rng = np.random.default_rng(2)
  x = jnp.asarray(rng.normal(size=(2, 10, 3, 128)), jnp.float32)
  pos = jnp.arange(5, 15)
  plain = fam.rotate(x, pos, 1e7)
  sectioned = fam.rotate(x, jnp.stack([pos] * 3), 1e7, (16, 24, 24))
  np.testing.assert_array_equal(np.asarray(plain), np.asarray(sectioned))
  np.testing.assert_allclose(
      tfm._rotary(x, jnp.broadcast_to(pos, (2, 10)), 1e7), plain, atol=1e-5)
  # components that differ rotate their own sections only
  other = fam.rotate(x, jnp.stack([pos, pos + 3, pos]), 1e7, (16, 24, 24))
  diff = np.asarray(jnp.abs(other - plain)).max(axis=(0, 1, 2))
  moved = np.flatnonzero(diff > 1e-6)
  assert set(moved) <= set(range(16, 40)) | set(range(80, 104)) and len(moved)


# -- the fields' defaults are today's programs --------------------------------


#: sha256 (16 hex) of the dead-code-eliminated jaxpr of the serving programs
#: of three accepted cells' models at toy sizes, as commit 93217c4 (the parent
#: of the PR that brought the selection) traced them
TODAYS_PROGRAMS = {
    "deepseek.prefill16": "0d360a214664abd1",
    "deepseek.step_many": "166bf9a20e3c79ff",
    "gpt2.prefill16": "f41f26aad9ae616f",
    "gpt2.step_many": "b74b81813d34ccf0",
    "trinity.prefill16": "cc3dab90807b2faf",
    "trinity.step_many": "4891cc6f59f11c58",
}


def _fingerprint(fn, *args):
  from jax._src.interpreters import partial_eval as pe
  closed = jax.make_jaxpr(fn)(*args)
  jaxpr, _ = pe.dce_jaxpr(closed.jaxpr, [True] * len(closed.jaxpr.outvars))
  return hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]


def _fingerprints(name, cfg):
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
    ("gpt2", None, None),
    ("trinity", "trinity-large-preview", "trinity_family"),
    ("deepseek", "deepseek-v3", "deepseek_v3_family"),
])
def test_the_new_fields_at_their_defaults_are_todays_programs(
    model, config, family):
  """A GPT-2 block's, Trinity's (GQA with windows, rings and sigmoid experts)
  and DeepSeek-V3's (latent layers, a group limit) decode step and prefill
  chunk trace to the programs they were before ``sparse_topk``, the indexer's
  fields and ``experts_score`` existed."""
  if family is None:
    cfg = tfm.TransformerConfig(
        vocab_size=97, num_layers=2, num_heads=4, d_model=64, d_ff=128,
        max_seq_len=128, remat=False, dtype=jnp.bfloat16)
  else:
    cfg = importlib.import_module(family).program_config(
        _rehearsal(config), 128, dtype=jnp.bfloat16)
  assert _fingerprints(model, cfg) == {
      k: v for k, v in TODAYS_PROGRAMS.items() if k.startswith(model + ".")}


# -- refusals, by name --------------------------------------------------------


_PLAIN = dict(vocab_size=97, num_layers=2, num_heads=4, d_model=32, d_ff=64,
              max_seq_len=64, remat=False)
_SPARSE = dict(_PLAIN, sparse_topk=8, index_heads=2, index_head_dim=8)


@pytest.mark.parametrize("kwargs,reason", [
    (dict(attention_window=16), "a selection among the positions of a window"),
    (dict(layer_windows=(16, 0)), "a selection among the positions of a window"),
    (dict(layer_sink=(True, False)), "a sink's share of a selected softmax"),
    (dict(layer_kv_heads=(2, 4)), "leaves of two widths or head counts"),
    (dict(attn_v_head_dim=4), "leaves of two widths or head counts"),
    (dict(loop_passes=2), "an index-key leaf a pass is not built"),
    (dict(kv_cache_dtype="int8"), "an int8 cache beside a bf16 index-key leaf"),
    (dict(kv_page_size=8, kv_num_pages=9, kv_pages_per_slot=8),
     "a third pool of index keys"),
    (dict(use_ring_attention=True), "laid out for ONE device"),
    (dict(layer_types=("mla", "attn")), "a latent or a recurrent layer that "
     "selects is not built"),
    (dict(moe_experts=4), "the trained MoE block"),
], ids=lambda x: None if isinstance(x, str) else "-".join(x))
def test_config_refuses_by_name_what_is_not_built_over_a_selection(
    kwargs, reason):
  tfm.TransformerConfig(**_SPARSE)
  with pytest.raises(ValueError, match="a learned indexer chooses.*"
                     + reason.replace("(", r"\(")):
    tfm.TransformerConfig(**dict(_SPARSE, **kwargs))


def test_config_checks_the_new_fields():
  for bad, msg in (
      (dict(sparse_topk=-1), "sparse_topk must be >= 0"),
      (dict(index_heads=2), "belong to a selection"),
      (dict(sparse_topk=8), "needs index_heads >= 1"),
      (dict(sparse_topk=8, index_heads=2, index_head_dim=7), "an even "),
      (dict(sparse_topk=8, index_heads=2, index_head_dim=256), "at most 128"),
      (dict(experts_score="tanh"), "'sigmoid' or 'softmax'"),
  ):
    with pytest.raises(ValueError, match=msg):
      tfm.TransformerConfig(**dict(_PLAIN, **bad))
  experts = dict(_PLAIN, ffn_types=("experts",) * 2, experts_total=16,
                 experts_held=4, experts_top_k=4, experts_d_ff=32)
  tfm.TransformerConfig(**dict(experts, experts_score="softmax"))
  with pytest.raises(ValueError, match="a group limit over softmax scores is "
                     "not built"):
    tfm.TransformerConfig(**dict(experts, experts_score="softmax",
                                 experts_groups=4, experts_groups_kept=2))


def test_training_a_selection_is_refused_by_name(toy):
  with pytest.raises(ValueError, match="a training state.*is served, not "
                     "trained"):
    tfm.create_state(jax.random.PRNGKey(0), toy["cfg"], seq_len=16)


@pytest.mark.parametrize("kwargs,reason", [
    (dict(spec_depth=2), "a selection over a verify window is not built"),
    (dict(page_size=8), "a third pool of index keys"),
    (dict(page_size=8, prefix_pages=4), "index keys"),
])
def test_engine_refuses_what_a_selection_cannot_take(toy, kwargs, reason):
  with pytest.raises(ValueError, match=reason):
    serving.ServingEngine(toy["params"], toy["cfg"], num_slots=2, **kwargs)


def test_a_mesh_refuses_a_layer_that_selects(toy):
  from types import SimpleNamespace
  mesh = SimpleNamespace(size=4, shape={})
  with pytest.raises(ValueError, match="a serving slab over a mesh of 4"):
    SlotDecoder(toy["cfg"], 2, mesh=mesh)
  attn = tfm.Attention(toy["cfg"], mesh)
  with pytest.raises(ValueError, match="a mesh of 4 devices.*ONE device"):
    attn.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 64)),
              jnp.arange(8)[None])


def test_a_verify_window_over_a_selection_is_refused(toy):
  model = tfm.Transformer(toy["cfg"])
  cache = jax.tree_util.tree_map_with_path(
      lambda p, x: jnp.zeros((2,), x.dtype) if p[-1].key == "index" else x,
      tfm._zero_cache(model, 2))
  with pytest.raises(ValueError, match="a block of 3 tokens a lane under "
                     "per-slot cursors"):
    model.apply({"params": toy["params"], "cache": cache}, _tokens(0, 2, 3),
                decode=True, mutable=["cache"])


# -- the engine ----------------------------------------------------------------


def test_engine_serves_and_counts(toy):
  """Through ``ServingEngine`` (padded plan, horizon, the step in flight):
  every request's tokens are the reference's greedy choices and the
  selection's counters are in ``stats`` under their names."""
  rng = np.random.default_rng(17)
  prompts = [rng.integers(0, VOCAB, n, dtype=np.int32) for n in (9, 23, 40, 16,
                                                                 5)]
  eng = serving.ServingEngine(toy["params"], toy["cfg"], num_slots=2,
                              max_restarts=0).start()
  try:
    rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    outs = [eng.result(r, timeout=300) for r in rids]
    stats = dict(eng.stats)
  finally:
    eng.stop()
  for p, seq in zip(prompts, outs):       # a result is prompt + tokens
    seq = np.asarray(seq, np.int32)
    assert len(seq) == len(p) + 6
    z = fam.reference_logits(toy["weights"], seq[None], TOY)[0]
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(z, -1))[len(p) - 1:-1], seq[len(p):])
  assert stats["engine_restarts"] == 0
  assert stats["sparse_prefill_queries"] == sum(len(p) for p in prompts)
  assert stats["sparse_prefill_limited"] == sum(max(0, len(p) - TOPK)
                                                for p in prompts)
  assert stats["sparse_rows_kept"] > 0
  assert stats["sparse_rows_kept"] <= stats["sparse_rows_candidate"]
  assert 0 < stats["sparse_queries_limited"] <= stats["live_slot_steps"]
  assert stats["decode_attn_reads_sparse"] == stats["decode_attn_reads"] > 0
  assert stats["index_rows_read"] \
      == stats["decode_attn_reads_sparse"] * 2 * MAX_SEQ
  assert stats["moe_assignments_held"] > 0
