"""A model whose attention layers differ in KIND beyond their window
(``TransformerConfig.layer_kv_heads`` / ``layer_rope_theta`` / ``layer_sink``:
window layers with 2 KV heads, a rotary base of 1e4 and a learned SINK in
their softmax beside full layers with 1 KV head and a base of 5e6), whose
heads have keys of one width and values of another (``attn_v_head_dim``),
scaled values (``attn_value_scale``) and a third of each head rotated
(``rope_dim``), with held sparse experts and no shared one, through the plain
forward, the cached decode and the serving slab (a RING of the window's rows
beside a whole-context leaf: FOUR leaf shapes), against the plain reference in
``mimo_v2_flash_family.py`` (a byte-for-byte copy of
``benchmarks/families/mimo_v2_flash.py``: float32, a full forward with the
window as a mask and the sink as a dropped softmax column, no cache, no ring,
none of the program's code). Seeded weights, toy widths, CPU.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mimo_v2_flash_family as fam
from tensorflowonspark_tpu import ops, serving
from tensorflowonspark_tpu.models import experts as experts_mod
from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.ops.flash_attention import NEG_INF, merge_partials
from tensorflowonspark_tpu.parallel import expert_parallel as ep
from tensorflowonspark_tpu.serving.slots import SlotDecoder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "benchmarks", "configs",
                       "mimo-v2-flash.json")) as _f:
  _FILE = json.load(_f)
#: the cell's rehearsal sizes: 1 dense + 6 expert layers (full, window x4,
#: full, window), hidden 64, 4 heads with keys of 24 and values of 16, 8
#: rotated dims, 1 KV head in a full layer and 2 in a window layer, window 8
#: (a ring of 16 rows), 4 held of 32 experts, 2 a token
TOY = dict({k: v for k, v in _FILE.items() if k != "rehearse"},
           **_FILE["rehearse"])
MAX_SEQ = 96
VOCAB = TOY["vocab_size"]
WINDOW_LAYERS, FULL_LAYERS = (1, 2, 3, 4, 6), (0, 5)

#: float32 on both sides, the same mathematics: what is left is summation
#: order (logits are of order 4; measured 1e-5)
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


def _block(cfg, layer, **kind):
  """The program's expert ``Block`` of layer ``layer``'s kind (``kind``
  overrides one of its attention's own settings)."""
  own = dict(window=cfg.layer_windows[layer], kv_heads=cfg.layer_kv_heads[layer],
             theta=cfg.layer_rope_theta[layer], sink=cfg.layer_sink[layer])
  own.update(kind)
  return tfm.Block(cfg, None, False, "attn", "experts", own["window"], True,
                   own["kv_heads"], own["theta"], own["sink"])


# -- the layers and the whole model against the reference ---------------------


def test_the_toy_is_the_published_pattern_at_toy_widths(toy):
  cfg = toy["cfg"]
  assert cfg.layer_windows == (0, 8, 8, 8, 8, 0, 8)
  assert cfg.layer_kv_heads == (1, 2, 2, 2, 2, 1, 2)
  assert cfg.layer_rope_theta == (5e6, 1e4, 1e4, 1e4, 1e4, 5e6, 1e4)
  assert cfg.layer_sink == (False, True, True, True, True, False, True)
  assert (cfg.head_dim, cfg.v_head_dim, cfg.rope_dim) == (24, 16, 8)
  assert cfg.attn_value_scale == 0.707 and cfg.wide_heads
  assert cfg.ffn_types == ("mlp",) + ("experts",) * 6
  assert cfg.experts_shared == 0 and cfg.experts_scale == 1.0
  # the published sizes: 64 of 192 dims rotate, 4 and 8 KV heads
  z = fam.sizes({k: v for k, v in _FILE.items() if k != "rehearse"})
  assert z["rotary"] == 64 and set(z["kv_heads"]) == {4, 8}
  assert fam.param_count(_FILE) == _FILE["parameters_as_built"]
  # a sink only where the kind has one: 5 window layers x 4 heads
  sinks = [k for k in jax.tree_util.tree_leaves_with_path(toy["params"])
           if "sink" in jax.tree_util.keystr(k[0])]
  assert len(sinks) == 5 and all(x.shape == (4,) for _, x in sinks)


@pytest.mark.parametrize("layer", [1, 5], ids=["window", "full"])
def test_a_layer_equals_the_references_layer(toy, layer):
  """One expert ``Block`` of each attention kind over a random stream 3
  windows long, with norm scales that are NOT all ones: the window layer
  masks, has 2 KV heads, rotates at 1e4 and has a sink; the full layer has 1
  KV head, rotates at 5e6 and has none; both have values of 16 against keys
  of 24, scaled by 0.707, and rotate 8 of 24 dims."""
  cfg, config = toy["cfg"], toy["config"]
  keys = jax.random.split(jax.random.PRNGKey(3), 3)
  scales = {n: 1.0 + 0.3 * jax.random.normal(k, toy["weights"][n].shape)
            for n, k in zip(("ln1", "ln2"), keys)}
  weights = dict(toy["weights"], **scales)
  tree = fam._to_program_tree(weights, fam.sizes(config))["layer_%d" % layer]
  assert ("sink" in tree["attn"]) == (layer == 1)
  assert tree["attn"]["v"]["kernel"].shape == (64, 2 if layer == 1 else 1, 16)
  assert tree["attn"]["out"]["kernel"].shape == (4, 16, 64)
  x = jax.random.normal(keys[2], (2, 24, 64))
  positions = jnp.broadcast_to(jnp.arange(24), (2, 24))
  got = _block(cfg, layer).apply({"params": tree}, x, positions)
  want = fam.reference_layer(weights, x, config, layer)
  np.testing.assert_allclose(got, want, atol=F32_ATOL)
  # each part matters: another value scale, all dims rotated, the other
  # kind's rotary base, and (the window layer) no window or no sink, differ
  for less in (dataclasses.replace(cfg, attn_value_scale=1.0),
               dataclasses.replace(cfg, rope_dim=0)):
    other = _block(less, layer).apply({"params": tree}, x, positions)
    assert float(jnp.max(jnp.abs(other - want))) > 0.02
  kinds = [dict(theta=cfg.layer_rope_theta[6 - layer])]
  if layer == 1:
    kinds += [dict(window=0)]
    no_sink = dict(tree, attn={k: v for k, v in tree["attn"].items()
                               if k != "sink"})
    other = _block(cfg, layer, sink=False).apply({"params": no_sink}, x,
                                                 positions)
    assert float(jnp.max(jnp.abs(other - want))) > 0.02
    np.testing.assert_allclose(
        other, fam.reference_layer(weights, x, config, layer, "no_sink"),
        atol=F32_ATOL)
  for kind in kinds:
    other = _block(cfg, layer, **kind).apply({"params": tree}, x, positions)
    assert float(jnp.max(jnp.abs(other - want))) > 1e-3, kind


def test_full_forward_equals_the_reference(toy):
  """1 + 6 layers over a sequence 7 windows long, logits from the untied
  head."""
  toks = _tokens(1, 2, 56)
  got = jax.jit(lambda t: tfm.Transformer(toy["cfg"]).apply(
      {"params": toy["params"]}, t))(toks)
  want = fam.reference_logits(toy["weights"], toks, toy["config"])
  np.testing.assert_allclose(got, want, atol=F32_ATOL)


# -- the sink: one more term of the denominator, counted once -----------------


def _column_softmax(q, k, v, keep, sink):
  """The reference's way: the sink as one more COLUMN, dropped after."""
  s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / q.shape[-1] ** 0.5
  s = jnp.where(keep[None, None], s, -1e30)
  col = jnp.broadcast_to(sink[None, :, None, None], s.shape[:-1] + (1,))
  p = jax.nn.softmax(jnp.concatenate([s, col], -1), -1)[..., :-1]
  return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def test_sink_identity_over_one_block_and_over_merged_partials():
  """``o_plain x sigmoid(lse - b)`` IS the softmax with ``exp(b)`` in its
  denominator: over one block, and over partials merged by
  ``merge_partials`` with the rescale applied ONCE after the last merge
  (applied to each partial before the merge, the sink would be counted
  twice: that differs)."""
  b, s, h, dk, dv = 2, 32, 4, 24, 16
  keys = jax.random.split(jax.random.PRNGKey(11), 4)
  q, k = (jax.random.normal(kk, (b, s, h, dk)) for kk in keys[:2])
  v = jax.random.normal(keys[2], (b, s, h, dv))
  sink = 1.0 + jax.random.normal(keys[3], (h,))
  at = jnp.arange(s)
  keep = at[None, :] <= at[:, None]
  want = _column_softmax(q, k, v, keep, sink)

  def partial(lo, hi):
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k[:, lo:hi]) / dk ** 0.5
    sc = jnp.where(keep[None, None, :, lo:hi], sc, NEG_INF)
    lse = jax.nn.logsumexp(sc, axis=-1)                       # [b, h, s]
    lse = jnp.where(jnp.any(keep[:, lo:hi], -1)[None, None], lse, NEG_INF)
    p = jnp.where(sc <= NEG_INF, 0.0, jnp.exp(sc - jnp.where(
        lse <= NEG_INF, 0.0, lse)[..., None]))
    return jnp.einsum("bhqk,bkhd->bqhd", p, v[:, lo:hi]), lse

  one = tfm._sink_rescale(*partial(0, s), sink)
  np.testing.assert_allclose(one, want, atol=1e-5)
  merged = merge_partials(*partial(0, 8), *partial(8, 24))
  merged = merge_partials(*merged, *partial(24, s))
  np.testing.assert_allclose(tfm._sink_rescale(*merged, sink), want,
                             atol=1e-5)
  twice = merge_partials(
      tfm._sink_rescale(*partial(0, 16), sink), partial(0, 16)[1],
      tfm._sink_rescale(*partial(16, s), sink), partial(16, s)[1])[0]
  assert float(jnp.max(jnp.abs(twice - want))) > 0.01
  # the dense full-forward helper writes the denominator out: the same
  got = tfm._full_attention_sink(q, k, v, 0, sink)
  np.testing.assert_allclose(got, want, atol=1e-5)


# -- the slab: four leaf shapes under one cursor a layer ----------------------


def test_slab_holds_four_leaf_shapes(toy):
  """A full layer: ``max_seq`` rows of 1 x 24 (K) and 1 x 16 (V) lanes; a
  window layer: a ring of 16 rows of 2 x 24 and 2 x 16 lanes; no head or row
  padded. The prefill's row keeps every position in every layer."""
  cfg = toy["cfg"]
  assert cfg.ring_rows(8) == 16 and cfg.ring_layers == WINDOW_LAYERS
  big = dataclasses.replace(cfg, max_seq_len=16384)
  assert big.ring_rows(128) == 128            # ONE block of the decode kernel
  dec = SlotDecoder(cfg, 3)
  assert dec.ring_windows == (8,) * 5 and dec.slab_cfg.kv_ring
  slabs = dec.init_slabs()
  shapes = set()
  for i in range(7):
    leaves = slabs["layer_%d" % i]["attn"]
    rows, hk = (16, 2) if i in WINDOW_LAYERS else (MAX_SEQ, 1)
    assert leaves["cached_k"].shape == (3, rows, hk * 24)
    assert leaves["cached_v"].shape == (3, rows, hk * 16)
    assert leaves["index"].shape == (3,)
    shapes |= {leaves["cached_k"].shape, leaves["cached_v"].shape}
  assert len(shapes) == 4
  row = tfm._zero_cache(dec.model, 1)
  assert sorted({x.shape for x in jax.tree.leaves(row) if x.ndim == 3}) == [
      (1, MAX_SEQ, 16), (1, MAX_SEQ, 24), (1, MAX_SEQ, 32), (1, MAX_SEQ, 48)]


@pytest.mark.parametrize("n", [11, 16, 37])
def test_insert_puts_position_p_in_row_p_mod_r(toy, n):
  """A positional row whose entry at position p IS p, inserted at cursor n:
  ring row r holds the newest position below n that is r modulo 16, in the
  K leaf (48 lanes) and in the V leaf (32 lanes) alike; a full layer's leaf
  is the row itself."""
  dec = SlotDecoder(toy["cfg"], 2)
  row = jax.tree.map(
      lambda x: jnp.broadcast_to(
          jnp.arange(MAX_SEQ, dtype=jnp.float32)[None, :, None], x.shape)
      if x.ndim == 3 else jnp.asarray(n, x.dtype),
      tfm._zero_cache(dec.model, 1))
  slabs = dec.insert(dec.init_slabs(), row, 1)
  for name in ("cached_k", "cached_v"):
    ring = np.asarray(slabs["layer_1"]["attn"][name])[1, :, -1]
    for p in range(max(0, n - 16), n):
      assert ring[p % 16] == p, (name, p, ring)
    np.testing.assert_array_equal(
        np.asarray(slabs["layer_5"]["attn"][name])[1, :, 0],
        np.arange(MAX_SEQ))
  assert [int(x[1]) for x in jax.tree.leaves(slabs) if x.ndim == 1] == [n] * 7


@pytest.mark.parametrize("window", [8, 16], ids=["ring16-window8",
                                                 "ring16-window16"])
def test_padded_prefill_then_a_wrapping_ring_equals_the_full_forward(window):
  """Two prompts, 11 tokens (below the ring) and 37 (past it: prefilled by
  the PADDED plan in chunks of 16, 16 and a tail of 5 padded to 8, rows past
  the cursor written), inserted and decoded by ``step_many`` at horizon 4 for
  12 tokens: the short slot's ring fills and wraps inside a horizon (cursor
  11 -> 23 over 16 rows), the long one wraps from the first step. Every
  served token is the reference's own first choice at its position (the
  reference's full forward, sink and all), the tokens equal each prompt's own
  ``greedy_generate_kv`` decode (a positional cache, the window a mask), and
  the counters sum the live lanes' contexts and window rows. Window 16 fills
  the ring exactly: the ONE row outside the window is the row the step is
  about to overwrite."""
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
  # 14 leaves (K and V of 7 layers) and 7 reads a step, 5 of them over a
  # ring, horizon 4; none by a kernel on the CPU
  assert dec.cursor_writes[4] == (14 * 4, 0)
  assert dec.attn_reads[4] == (7 * 4, 0, 5 * 4)
  cursors = [len(p) + j for p in prompts for j in range(budget - 1)]
  assert totals["context"] == sum(cursors)
  assert totals["window_context"] == sum(min(c, window) for c in cursors)
  assert 0 < totals["touched"] <= totals["held"] <= 2 * 6 * len(cursors)
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
  (every position kept, the window a mask, K and V leaves of two widths): the
  logits at EVERY position are the reference's full forward's."""
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


@pytest.mark.parametrize("hk,rows,window,cursors", [
    (8, 128, 128, (0, 5, 127, 128, 129, 600, 1000)),   # a ring of ONE block
    (4, 384, 0, (0, 5, 127, 128, 129, 300, 384)),      # a whole-context leaf
], ids=["ring-8kv-sink", "full-4kv"])
def test_decode_kernel_with_keys_of_192_and_values_of_128(
    monkeypatch, hk, rows, window, cursors):
  """``_cached_attention`` over bf16 leaves of ``hk x 192`` (K) and ``hk x
  128`` (V) lanes, 16 query heads, through ``ops.decode_attention``
  (interpret mode) and through the dense contraction: the same numbers. The
  window layer's leaf is a ring of 128 rows = ONE block with a sink a head;
  once it is full the ONE row the step is about to overwrite (position
  ``cursor - 128``) is excluded: filling it with huge keys changes nothing."""
  b, h, dk, dv = len(cursors), 16, 192, 128
  ring = bool(window)
  keys = jax.random.split(jax.random.PRNGKey(5), 6)
  q = jax.random.normal(keys[0], (b, 1, h, dk)).astype(jnp.bfloat16)
  k = jax.random.normal(keys[1], (b, 1, hk, dk)).astype(jnp.bfloat16)
  v = jax.random.normal(keys[2], (b, 1, hk, dv)).astype(jnp.bfloat16)
  ck = jax.random.normal(keys[3], (b, rows, hk * dk)).astype(jnp.bfloat16)
  cv = jax.random.normal(keys[4], (b, rows, hk * dv)).astype(jnp.bfloat16)
  sink = 2.0 + jax.random.normal(keys[5], (h,)) if ring else None
  cursor = jnp.asarray(cursors, jnp.int32)
  if ring:
    skip = np.asarray(tfm._ring_skip(cursor, rows, window))
    assert [int(x) for x in skip[1]] == [0, 0, 0, 1, 1, 1, 1]
    for i in range(b):
      for j in range(int(skip[1, i])):
        ck = ck.at[i, (int(skip[0, i]) + j) % rows].set(50.0)
  assert ops.decode_attention_supports((b, h, dk), q.dtype, ck.shape,
                                       ck.dtype, cv.shape)
  args = dict(q_pos=cursor[:, None], window=window, lengths=cursor, ring=ring,
              sink=sink)
  dense = tfm._cached_attention(q, k, v, ck, cv, **args)
  assert dense.shape == (b, 1, h, dv)
  monkeypatch.setenv("TOS_PALLAS_INTERPRET", "0")
  monkeypatch.setattr(ops, "pallas_interpret", lambda: True)
  with tfm.decode_attention_tally() as reads:
    kernel = tfm._cached_attention(q, k, v, ck, cv, **args)
  assert reads == {"reads": 1, "ragged": 1, "ring": int(ring)}
  # bf16 outputs of the same f32 mathematics: one rounding apart at most
  np.testing.assert_allclose(np.asarray(kernel, np.float32),
                             np.asarray(dense, np.float32), atol=2e-2)
  # against the plain softmax over each slot's window, position by position,
  # the sink an extra column
  for i, c in enumerate(cursors):
    held = [p for p in range(max(0, c - rows), c)
            if not window or p > c - window]
    kk = jnp.concatenate([ck[i, [p % rows for p in held]].reshape(-1, hk, dk),
                          k[i]]).astype(jnp.float32)
    vv = jnp.concatenate([cv[i, [p % rows for p in held]].reshape(-1, hk, dv),
                          v[i]]).astype(jnp.float32)
    s = jnp.einsum("hd,thd->ht", q[i, 0].astype(jnp.float32),
                   jnp.repeat(kk, h // hk, axis=1)) / dk ** 0.5
    if ring:
      s = jnp.concatenate([s, sink[:, None]], axis=1)
    p = jax.nn.softmax(s, -1)[:, :len(held) + 1]
    want = jnp.einsum("ht,thd->hd", p, jnp.repeat(vv, h // hk, axis=1))
    np.testing.assert_allclose(np.asarray(kernel[i, 0], np.float32), want,
                               atol=3e-2)
  # without the sink the window layer's numbers are others
  if ring:
    other = tfm._cached_attention(q, k, v, ck, cv, **dict(args, sink=None))
    assert float(jnp.max(jnp.abs(other.astype(jnp.float32)
                                 - kernel.astype(jnp.float32)))) > 0.05


def test_decode_kernel_support_is_by_the_two_widths():
  sup = ops.decode_attention_supports
  bf = jnp.bfloat16
  # the published shapes, 32 and 48 slots: both leaf kinds in VMEM
  for slots in (32, 48):
    assert sup((slots, 64, 192), bf, (slots, 128, 1536), bf,
               (slots, 128, 1024))
    assert sup((slots, 64, 192), bf, (slots, 16384, 768), bf,
               (slots, 16384, 512))
  # one width, as before (None = the K leaf's shape)
  assert sup((16, 20, 64), bf, (16, 1024, 1280), bf)
  assert sup((16, 20, 64), bf, (16, 1024, 1280), bf, (16, 1024, 1280))
  # a V leaf of other rows, a value head that neither divides nor is a
  # multiple of 128 lanes, a V leaf of part lanes
  assert not sup((4, 16, 192), bf, (4, 128, 768), bf, (4, 256, 512))
  assert not sup((4, 16, 192), bf, (4, 128, 768), bf, (4, 128, 768))
  assert not sup((4, 16, 192), bf, (4, 128, 768), bf, (4, 128, 64))


@pytest.mark.parametrize("window", [None, 16], ids=["full", "window"])
def test_flash_forward_with_keys_of_192_and_values_of_128(window):
  """``flash_attention_block`` (interpret mode) with 8 query heads over 2 KV
  heads, keys of 192 and values of 128, at a cursor: output and log-sum-exp
  against the dense mathematics."""
  b, sq, sk, h, hk, dk, dv = 1, 32, 64, 8, 2, 192, 128
  keys = jax.random.split(jax.random.PRNGKey(9), 3)
  q = jax.random.normal(keys[0], (b, sq, h, dk))
  k = jax.random.normal(keys[1], (b, sk, hk, dk))
  v = jax.random.normal(keys[2], (b, sk, hk, dv))
  q_base = 24
  out, lse = ops.flash_attention_block(q, k, v, q_base, 0, causal=True,
                                       interpret=True, window=window)
  assert out.shape == (b, sq, h, dv) and lse.shape == (b, h, sq)
  s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, h // hk, 2)) / dk ** 0.5
  qp, kp = q_base + jnp.arange(sq)[:, None], jnp.arange(sk)[None, :]
  keep = kp <= qp
  if window:
    keep = jnp.logical_and(keep, kp > qp - window)
  s = jnp.where(keep[None, None], s, -1e30)
  want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                    jnp.repeat(v, h // hk, 2))
  np.testing.assert_allclose(out, want, atol=2e-5)
  np.testing.assert_allclose(lse, jax.nn.logsumexp(s, -1), atol=2e-5)


def test_flash_backward_refuses_heads_of_two_widths():
  q = jnp.ones((1, 16, 2, 24))
  v = jnp.ones((1, 16, 2, 16))
  with pytest.raises(ValueError, match="only the forward is built"):
    jax.grad(lambda vv: ops.flash_attention(q, q, vv, interpret=True).sum())(v)


def test_prefill_chunks_go_through_the_flash_forward(monkeypatch):
  """A row cache of several ``_ROW_BLOCK``s (32 rows here), attention forced
  to the kernels (interpret mode): the FIRST chunk attends itself through the
  flash forward (a window layer's with its sink from ``(o, lse)``), the later
  chunks attend the row through ``flash_attention_block`` block by block, the
  sink applied once after the last merge; the logits at every position are
  the reference's full forward's and the dense branch is never traced."""
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
  # and the plain forward under a forced kernel takes the flash forward too
  got = model.apply({"params": toy["params"]}, toks)
  np.testing.assert_allclose(got, want, atol=F32_ATOL)


# -- the share of an expert-parallel deployment --------------------------------


def test_the_sixteen_shares_add_up_to_the_uncut_layer(toy):
  """The sizing guide's share test: 32 experts over 16 chips, 2 each. The
  routed parts the 16 shares compute (there is NO shared expert to count
  once) equal the uncut reference layer (all 32 held); every token's 2
  assignments are computed by exactly one share each."""
  uncut = dict(toy["config"], n_routed_experts=32)
  z_all = fam.sizes(uncut)
  w_all = fam._layer_weights(fam.make_weights(7, uncut), z_all, 1)
  # the layer's own [held, ...] stacks out of the model's [layers, held, ...]
  w_all.update({n: w_all[n][w_all["exp_at"]]
                for n in ("exp_gate", "exp_up", "exp_down")})
  x = jax.random.normal(jax.random.PRNGKey(8), (2, 24, 64))
  want = fam._experts(x, w_all, z_all, "f32")
  flat = x.reshape(-1, 64)
  experts, weights = ep.route_sigmoid_topk(
      flat, w_all["router"], w_all["router_bias"], z_all["top_k"], 1.0)
  total, assigned = 0.0, 0
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
    np.testing.assert_allclose(y.reshape(x.shape),
                               fam._experts(x, w, z, "f32"), atol=3e-5)
  np.testing.assert_allclose(total, want, atol=3e-5, rtol=3e-5)
  assert assigned == 2 * 24 * z_all["top_k"]
  # the layer's module is what computes a share in the program, with no
  # shared branch in its tree
  layer = toy["params"]["layer_1"]["moe"]
  assert "shared" not in layer
  got = experts_mod.HeldExperts(toy["cfg"]).apply({"params": layer}, x)
  np.testing.assert_allclose(
      got, fam._experts(x, fam._layer_weights(
          toy["weights"], fam.sizes(toy["config"]), 1),
                        fam.sizes(toy["config"]), "f32"), atol=3e-5)


# -- the fields' defaults are today's programs --------------------------------


def _texts(cfg, params=None):
  """The lowered text of a model's forward, of a padded prefill chunk and of
  the slab's ``step_many``, from shapes alone."""
  from flax.core import meta
  dec = SlotDecoder(cfg, 2)
  toks = jax.ShapeDtypeStruct((1, 16), jnp.int32)
  if params is None:
    params = jax.eval_shape(lambda: meta.unbox(dec.model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
  row = jax.eval_shape(lambda: tfm._zero_cache(dec.model, 1))
  slabs = jax.eval_shape(dec.init_slabs)
  n = jax.ShapeDtypeStruct((2,), jnp.int32)
  live = jax.ShapeDtypeStruct((2,), jnp.bool_)
  fwd = jax.jit(lambda p, t: tfm.Transformer(cfg).apply({"params": p}, t))
  return params, (
      fwd.lower(params, toks).as_text(),
      jax.jit(dec._prefill_impl).lower(
          params, row, toks, jax.ShapeDtypeStruct((), jnp.int32)).as_text(),
      dec.step_many_jit(4).lower(params, slabs, n, live, n).as_text())


def _todays_toys():
  import kimi_linear_family
  import ouro_family
  import trinity_family
  from test_kimi_linear import TOY as kimi
  from test_ouro import TOY as ouro
  from test_trinity import TOY as trinity
  return {
      "gpt2": lambda: tfm.TransformerConfig(
          vocab_size=97, num_layers=2, num_heads=4, num_kv_heads=2,
          d_model=32, d_ff=64, max_seq_len=48, remat=False,
          dtype=jnp.float32),
      "kimi": lambda: kimi_linear_family.program_config(kimi, 128),
      "ouro": lambda: ouro_family.program_config(ouro, 96),
      "trinity": lambda: trinity_family.program_config(trinity, 96),
  }


@pytest.mark.parametrize("model", ["gpt2", "kimi", "ouro", "trinity"])
def test_the_new_fields_at_their_defaults_are_todays_programs(model):
  """A toy of each family the benchmark serves, as its family builds it
  (naming none of the new fields) and with every new field SPELLED OUT at
  what its default means (values as wide as keys, every layer the model's KV
  head count and rotary base, all of a head rotated, no sink, values
  unscaled): the same parameter tree, and the forward, a prefill chunk and
  the slab's ``step_many`` lower to the same text."""
  base = _todays_toys()[model]()
  n = base.num_layers
  assert not base.wide_heads
  assert (base.attn_v_head_dim, base.layer_kv_heads, base.rope_dim,
          base.layer_rope_theta, base.layer_sink, base.attn_value_scale) \
      == (0, (), 0, (), (), 1.0)
  said = dataclasses.replace(
      base, attn_v_head_dim=base.head_dim,
      layer_kv_heads=(base.kv_heads,) * n, rope_dim=base.head_dim,
      layer_rope_theta=(base.rope_theta,) * n, layer_sink=(False,) * n,
      attn_value_scale=1.0)
  assert not said.wide_heads
  params, want = _texts(base)
  params_said, got = _texts(said)
  assert jax.tree.map(lambda x: x.shape, params_said) \
      == jax.tree.map(lambda x: x.shape, params)
  for a, b in zip(got, want):
    assert a == b
  if model == "gpt2":
    # and each of them changes the programs when it says something else
    for field, value in (("attn_v_head_dim", 16), ("layer_kv_heads", (4, 2)),
                         ("rope_dim", 4), ("layer_rope_theta", (5e6, 1e4)),
                         ("layer_sink", (True, False)),
                         ("attn_value_scale", 0.707)):
      other = _texts(dataclasses.replace(base, **{field: value}))[1]
      assert other[0] != want[0], field


def test_config_checks_the_new_fields():
  kw = dict(vocab_size=97, num_layers=2, num_heads=4, d_model=32, d_ff=64,
            max_seq_len=48)
  for bad in (dict(layer_kv_heads=(2,)), dict(layer_rope_theta=(1e4, -1.0)),
              dict(layer_sink=(True,))):
    with pytest.raises(ValueError, match="each of the 2 layers"):
      tfm.TransformerConfig(**kw, **bad)
  with pytest.raises(ValueError, match="must divide num_heads"):
    tfm.TransformerConfig(**kw, layer_kv_heads=(3, 2))
  for bad in (dict(rope_dim=3), dict(rope_dim=10), dict(attn_v_head_dim=-1)):
    with pytest.raises(ValueError, match="rope_dim an even number"):
      tfm.TransformerConfig(**kw, **bad)


# -- what is new and not built is refused, by name ----------------------------


@pytest.mark.parametrize("changes,reason", [
    (dict(kv_cache_dtype="int8", layer_windows=()),
     "untried for leaves of two widths"),
    (dict(kv_page_size=16, kv_num_pages=8, kv_pages_per_slot=6,
          layer_windows=()), "pages of two widths or two head counts"),
    (dict(use_ring_attention=True), "a mesh for such heads is not built"),
])
def test_config_refuses_what_such_heads_cannot_take(toy, changes, reason):
  with pytest.raises(ValueError, match=reason) as err:
    dataclasses.replace(toy["cfg"], **changes)
  assert "attn_v_head_dim" in str(err.value)
  # each of the three alone is such a model
  kw = dict(vocab_size=97, num_layers=2, num_heads=4, d_model=32, d_ff=64,
            max_seq_len=48, kv_cache_dtype="int8")
  for alone in (dict(attn_v_head_dim=4), dict(layer_kv_heads=(2, 4)),
                dict(layer_sink=(True, False))):
    with pytest.raises(ValueError, match="untried for leaves of two widths"):
      tfm.TransformerConfig(**kw, **alone)


def test_a_mesh_refuses_such_heads(toy):
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib
  mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=-1, tensor=2))
  with pytest.raises(ValueError, match="a mesh for such heads is not built"):
    tfm.Transformer(dataclasses.replace(toy["cfg"], vocab_size=256),
                    mesh=mesh).init(
        jax.random.PRNGKey(0), jnp.zeros((4, 16), jnp.int32))


@pytest.mark.parametrize("kwargs,reason", [
    (dict(page_size=16), "a pool of two lifetimes"),
    (dict(page_size=16, prefix_pages=4), "prefix sharing over window layers"),
    (dict(spec_depth=2), "speculation over a ring"),
])
def test_engine_refuses_what_a_ring_cannot_take(toy, kwargs, reason):
  """What a ring refuses is refused for this model too: nothing stands in
  for the multi-token-prediction layers' speculation."""
  with pytest.raises(ValueError, match=reason) as err:
    serving.ServingEngine(toy["params"], toy["cfg"], num_slots=2, **kwargs)
  assert "layer_windows" in str(err.value)
  with pytest.raises(ValueError, match="int8 ring"):
    SlotDecoder(dataclasses.replace(toy["cfg"], kv_cache_dtype="int8",
                                    attn_v_head_dim=0, layer_kv_heads=(),
                                    layer_sink=()), 2)


# -- the engine ---------------------------------------------------------------


def test_engine_serves_past_the_window_and_counts(toy):
  """``ServingEngine`` on 2 slots with prompts below and past the window:
  every request's tokens are its own ``greedy_generate_kv`` decode, and the
  stats carry the step's sums (14 cursor writes and 7 reads a step, 5 of the
  reads over a ring)."""
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
  assert stats["cursor_leaf_writes"] == 14 * stats["steps"]
  assert stats["decode_attn_reads"] == 7 * stats["steps"]
  assert stats["decode_attn_reads_ring"] == 5 * stats["steps"]
  assert stats["engine_restarts"] == 0 and stats["replay_mismatches"] == 0


# -- the controls of the comparison that decides ``correct`` ------------------


@pytest.mark.parametrize("control", ["fp8", "no_sink"])
def test_a_control_fails_the_comparison_at_toy_width(toy, control):
  """What the benchmark's check computes (how far a served token's float32
  reference logit lies below the reference's best), on the program's greedy
  tokens and on a control's: the fp8 reference's first choices, and those of
  the reference WITHOUT its sinks. The program reads zero to rounding; each
  control lies far beyond the rehearsal's limits (mean 0.002, max 0.02)."""
  p = _tokens(50, 24)
  out = np.asarray(tfm.greedy_generate_kv(
      toy["params"], toy["cfg"], jnp.asarray(p)[None], 40))
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
