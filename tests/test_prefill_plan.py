"""The padded prefill plan (serving/slots.py): a prompt's tail padded up to
a bucket and masked by the cursor, so a prompt is one program where it can
be, whatever the model's cache kinds: a recurrent layer masks the padding
out of its state itself. CPU, toy widths, real models.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.tree_util import tree_flatten_with_path

import kimi_linear_family as fam
import trinity_family
from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.serving import (
    DEFAULT_BUCKETS, ServingEngine, SlotDecoder, chunk_plan, padded_plan)

#: the benchmark pool's twelve prompt lengths and their weights
#: (benchmarks/traffic/serve-backlog.json), and the plan each gets in a
#: 1024-long row
POOL = {16: [(16, 16)], 23: [(32, 23)], 33: [(64, 33)], 47: [(64, 47)],
        67: [(128, 67)], 95: [(128, 95)], 131: [(256, 131)],
        191: [(256, 191)], 263: [(512, 263)], 383: [(512, 383)],
        521: [(512, 512), (16, 9)], 768: [(512, 512), (256, 256)]}
POOL_WEIGHTS = (3, 4, 6, 8, 10, 12, 13, 11, 8, 6, 4, 3)
B = max(DEFAULT_BUCKETS)
#: the long-row pool's eight prompt lengths and their weights
#: (benchmarks/traffic/serve-backlog-16k.json), in rows of 16384
LONG_POOL = (256, 512, 1024, 2048, 4096, 6144, 8192, 12288)
LONG_POOL_WEIGHTS = (3, 5, 6, 6, 5, 4, 3, 2)
LONG_ROW = 16384


# -- the plan -----------------------------------------------------------------


@pytest.mark.parametrize("n,plan", sorted(POOL.items()) + [
    (1, [(16, 1)]),                        # below every bucket
    (B, [(B, B)]),                         # a whole bucket pads nothing
    (B + 1, [(B, B), (16, 1)]),
    (1023, [(B, B), (B, B - 1)]),          # the longest prompt a row takes
])
def test_padded_plan_of_a_fresh_row(n, plan):
  assert padded_plan(n, 1024) == plan


def test_pool_is_one_program_a_prompt_and_a_third_padding():
  chunks = [len(POOL[n]) for n in sorted(POOL)]
  per_prompt = np.average(chunks, weights=POOL_WEIGHTS)
  assert per_prompt == pytest.approx(1.08, abs=0.005)
  exact = np.average([len(chunk_plan(n)) for n in sorted(POOL)],
                     weights=POOL_WEIGHTS)
  assert exact == pytest.approx(4.91, abs=0.005)
  computed = np.average([sum(s for s, _ in POOL[n]) for n in sorted(POOL)],
                        weights=POOL_WEIGHTS)
  real = np.average(sorted(POOL), weights=POOL_WEIGHTS)
  assert 0.25 < 1 - real / computed < 0.35
  # the exact plan's four largest sizes as padded shapes would compute more
  four = np.average(
      [sum(s for s, _ in padded_plan(n, 1024, (512, 128, 32, 16)))
       for n in sorted(POOL)], weights=POOL_WEIGHTS)
  assert 0.45 < 1 - real / four < 0.55


@pytest.mark.parametrize("n,room,buckets,plan", [
    # the tail's bucket would pass the row's end: exact pieces until one fits
    (440, 464, DEFAULT_BUCKETS, [(256, 256), (128, 128), (64, 56)]),
    (100, 101, DEFAULT_BUCKETS, [(64, 64), (32, 32)] + [(1, 1)] * 4),
    (3, 8, DEFAULT_BUCKETS, [(1, 1)] * 3),
    (14, 48, (64,), [(1, 1)] * 14),        # no bucket fits the row at all
    (14, 48, (8, 4, 2, 1), [(8, 8), (8, 6)]),
    (5, 48, (4,), [(4, 4), (4, 1)]),
])
def test_padded_plan_falls_back_to_exact_pieces(n, room, buckets, plan):
  assert padded_plan(n, room, buckets) == plan


@pytest.mark.parametrize("offset", [0, 5, 48, 500, 1000])
def test_padded_plan_invariants_at_resume_offsets(offset):
  """Whatever the offset a resumed prefill starts at: every real token
  runs once, no chunk ends past the row (``dynamic_update_slice`` would
  clamp it over live entries), only the LAST chunk is padded, and the
  shapes are buckets (1 where none is small enough)."""
  max_seq = 1024
  for n in range(1, max_seq - offset):      # plen + 1 <= max_seq_len
    plan = padded_plan(n, max_seq - offset)
    assert sum(v for _, v in plan) == n
    assert all(s == v for s, v in plan[:-1]) and plan[-1][1] <= plan[-1][0]
    assert offset + sum(v for _, v in plan[:-1]) + plan[-1][0] <= max_seq
    assert {s for s, _ in plan} <= set(DEFAULT_BUCKETS) | {1}
    if offset == 0:
      assert len(plan) == -(-n // B)


def test_padded_plan_rejects_an_empty_prompt():
  with pytest.raises(ValueError, match="prompt length"):
    padded_plan(0, 1024)


# -- the ladder follows the row ---------------------------------------------------


def _row_decoder(max_seq):
  return SlotDecoder(_tiny(max_seq_len=max_seq), 1)


@pytest.mark.parametrize("max_seq,top", [
    (48, 512), (512, 512), (1024, 512), (4096, 512), (8191, 512),
    (8192, 1024), (16384, 2048), (32768, 4096)])
def test_the_ladder_of_shapes_follows_the_rows_length(max_seq, top):
  """Rows up to 4096 keep the six shapes to the letter; past that the
  ladder doubles upward while a shape is at most an eighth of the row, so
  no prompt is more than eight chunks in a row whose length is a power of
  two (a row that is no multiple of its shapes ends in exact pieces)."""
  dec = _row_decoder(max_seq)
  assert dec.padded_prefill
  assert dec.buckets[0] == top
  assert dec.buckets[-len(DEFAULT_BUCKETS):] == DEFAULT_BUCKETS
  assert all(a == 2 * b for a, b in zip(dec.buckets, dec.buckets[1:]))
  if max_seq <= 4096:
    assert dec.buckets == DEFAULT_BUCKETS
  if max_seq >= 512 and max_seq & (max_seq - 1) == 0:
    assert len(dec.plan(max_seq - 1)) == min(8, max_seq // 512)


def test_the_long_rows_ladder_spelled_out():
  dec = _row_decoder(LONG_ROW)
  assert dec.buckets == (2048, 1024, 512, 256, 128, 64, 32, 16)


@pytest.mark.parametrize("n,chunks", zip(LONG_POOL, (1, 1, 1, 1, 2, 3, 4, 6)))
def test_long_pool_prompt_is_whole_chunks_of_the_rows_ladder(n, chunks):
  """Each length of the long-row pool is a whole number of its shapes:
  nothing is padded, and the 12288-token prompt is 6 programs, each ending
  inside the row."""
  dec = _row_decoder(LONG_ROW)
  plan = dec.plan(n)
  assert len(plan) == chunks
  assert all(shape == valid for shape, valid in plan)
  assert sum(valid for _, valid in plan) == n
  assert {shape for shape, _ in plan} <= {256, 512, 1024, 2048}
  assert len(padded_plan(n, LONG_ROW)) == -(-n // B)      # what it was


def test_long_pool_is_two_programs_a_prompt_and_no_padding():
  dec = _row_decoder(LONG_ROW)
  plans = [dec.plan(n) for n in LONG_POOL]
  programs = np.dot([len(p) for p in plans], LONG_POOL_WEIGHTS)
  assert programs == 66
  assert programs / sum(LONG_POOL_WEIGHTS) == pytest.approx(1.94, abs=0.005)
  assert np.dot([-(-n // B) for n in LONG_POOL], LONG_POOL_WEIGHTS) == 228
  assert sum(s - v for p in plans for s, v in p) == 0
  # 26 of the 34 prompts, 97% of the prompt tokens, take the new shapes
  new = [w for p, w in zip(plans, LONG_POOL_WEIGHTS) if p[0][0] > B]
  assert sum(new) == 26
  tokens = np.dot(LONG_POOL, LONG_POOL_WEIGHTS)
  assert np.dot(LONG_POOL[2:], LONG_POOL_WEIGHTS[2:]) / tokens > 0.96


@pytest.mark.parametrize("offset", [0, 2048, 12288, 14336, 15000, 16000])
def test_long_rows_plan_ends_inside_the_row_at_any_offset(offset):
  """``room`` is respected under the longer ladder too: whatever is left of
  a 16384-row, no chunk ends past it and only the last is padded."""
  dec = _row_decoder(LONG_ROW)
  for n in (1, 17, 300, 1025, 2047, 2048, 2049, LONG_ROW - offset - 1):
    if not 1 <= n < LONG_ROW - offset:
      continue
    plan = dec.plan(n, offset)
    assert sum(v for _, v in plan) == n
    assert all(s == v for s, v in plan[:-1])
    assert offset + sum(v for _, v in plan[:-1]) + plan[-1][0] <= LONG_ROW
    assert {s for s, _ in plan} <= set(dec.buckets) | {1}


@pytest.mark.parametrize("n,plan", sorted(POOL.items()))
def test_short_rows_pool_plans_as_it_did(n, plan):
  """The 1024-row pool through ``SlotDecoder.plan``: the table above."""
  dec = _row_decoder(1024)
  assert dec.plan(n) == plan


# -- the program: padded against exact ----------------------------------------


def _tiny(**kw):
  kw.setdefault("dtype", jnp.float32)
  kw.setdefault("num_heads", 2)
  kw.setdefault("max_seq_len", 48)
  return tfm.TransformerConfig(vocab_size=64, num_layers=2, d_model=32,
                               d_ff=64, remat=False, **kw)


VARIANTS = {
    "f32": dict(),
    "bf16": dict(dtype=jnp.bfloat16),
    "int8_kv": dict(kv_cache_dtype="int8"),
    "gqa": dict(num_heads=4, num_kv_heads=2),
    "window": dict(attention_window=8),
    "flash": dict(attention_impl="flash"),   # the chip's fresh-row branch
}


def _written(leaf, n):
  """What the cursor covers of a row-cache leaf: entries past it are a
  padded tail's garbage by design."""
  leaf = np.asarray(leaf, np.float32)
  return leaf[:, :n] if leaf.ndim > 1 else leaf


def _decode_from(dec, params, cache, first, steps=6):
  slabs = dec.insert(dec.init_slabs(), cache, 0)
  toks, tok = [first], first
  for _ in range(steps):
    slabs, nxt = dec.step(params, slabs, [tok], [True])
    tok = int(np.asarray(nxt)[0])
    toks.append(tok)
  return toks


def _cursors(cache):
  return [int(leaf) for path, leaf in tree_flatten_with_path(cache)[0]
          if getattr(path[-1], "key", None) == "index"]


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_padded_prefill_equals_the_exact_plan(variant):
  """One padded program (and a whole chunk plus a padded one) against the
  exact decomposition: the same row cache on positions [0, n), the cursor
  at n, the same first token and the same decoded continuation."""
  cfg = _tiny(**VARIANTS[variant])
  params = tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=16).params
  n = 21
  prompt = np.random.RandomState(4).randint(1, 64, (n,)).astype(np.int32)
  exact = SlotDecoder(cfg, 1)
  exact.padded_prefill = False              # the test's own steering
  assert exact.plan(n, 0, (16, 4, 1)) == [(16, 16), (4, 4), (1, 1)]
  want_cache, want_first = exact.prefill(params, prompt, (16, 4, 1))
  want_stream = _decode_from(exact, params, want_cache, want_first)
  assert set(_cursors(want_cache)) == {n}

  dec = SlotDecoder(cfg, 1)
  assert dec.padded_prefill and dec.buckets == DEFAULT_BUCKETS
  tol = dict(atol=1e-5, rtol=1e-5)
  if variant == "bf16":
    tol = dict(atol=0.05, rtol=0.05)
  if variant == "int8_kv":
    tol = dict(atol=1.0, rtol=1e-4)          # one step of the int8 grid
  for buckets, plan in (((32, 16), [(32, n)]),
                        ((16, 8), [(16, 16), (8, n - 16)])):
    assert dec.plan(n, 0, buckets) == plan
    cache, first = dec.prefill(params, prompt, buckets)
    assert set(_cursors(cache)) == {n}
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(want_cache)):
      np.testing.assert_allclose(_written(a, n), _written(b, n), **tol)
    assert first == want_first
    assert _decode_from(dec, params, cache, first) == want_stream, buckets


def test_padded_tail_after_a_prefix_cache_resume():
  """Paged slab + shared prefix: the warm row ``gather_pages`` rebuilds
  holds the prefix's pages, the padded plan runs the TAIL from there, and
  ``insert_pages`` masks by the row's cursor (the true length), so the
  padding never reaches a pool page. Same tokens as a contiguous decode of
  the whole prompt."""
  cfg = _tiny()
  params = tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=16).params
  rng = np.random.RandomState(9)
  prefix = rng.randint(1, 64, (8,)).astype(np.int32)
  first_prompt = np.concatenate([prefix, rng.randint(1, 64, (3,))]).astype(
      np.int32)
  prompt = np.concatenate([prefix, rng.randint(1, 64, (5,))]).astype(np.int32)
  n, shared, ps = len(prompt), len(prefix), 4
  dec = SlotDecoder(cfg, 2, page_size=ps)
  slabs = dec.init_slabs()

  def table(pages):
    return pages + [0] * (dec.pages_per_slot - len(pages))

  row, _ = dec.prefill(params, first_prompt, (16, 8))
  slabs = dec.insert_pages(slabs, row, 0, table([1, 2, 3]))
  # the second request shares the prefix's two full pages and owns 3..
  pages = table([1, 2, 4, 5, 6])
  warm = dec.gather_pages(slabs, pages, shared)
  assert dec.plan(n - shared, shared, (16, 8)) == [(8, n - shared)]
  cache, first = dec.prefill(params, prompt, (16, 8), resume=(warm, shared))
  assert set(_cursors(cache)) == {n}

  whole = SlotDecoder(cfg, 1)
  whole.padded_prefill = False
  want_cache, want_first = whole.prefill(params, prompt, (8, 4, 1))
  for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(want_cache)):
    np.testing.assert_allclose(_written(a, n), _written(b, n), atol=1e-5,
                               rtol=1e-5)
  assert first == want_first
  want = _decode_from(whole, params, want_cache, want_first)

  slabs = dec.insert_pages(slabs, cache, 1, pages, start=shared)
  toks, tok = [first], first
  for _ in range(6):
    slabs, nxt = dec.step(params, slabs, [0, tok], [False, True])
    tok = int(np.asarray(nxt)[1])
    toks.append(tok)
  assert toks == want


def test_a_ladder_four_times_as_wide_prefills_the_same_row(monkeypatch):
  """A window + full model (tests/trinity_family.py at toy width: window 8,
  a row of three ``_ROW_BLOCK``s of 32, later chunks through the blocked
  flash kernel in interpret mode): a prompt prefilled in chunks of 16 and in
  chunks of 4 gives the same first token, the same row cache and the same
  decoded continuation out of the ring slab. The narrow plan has a chunk
  edge either side of the window (4, 12) and of a block edge (28, 36), the
  wide one past the window (16) and either side of the second block edge
  (48, 75)."""
  from test_trinity import MAX_SEQ, TOY
  monkeypatch.setattr(tfm, "_ROW_BLOCK", 32)
  cfg = trinity_family.program_config(TOY, MAX_SEQ, dtype=jnp.float32,
                                      attention_impl="flash")
  params = trinity_family.program_params(7, TOY)
  n = 75
  prompt = np.random.default_rng(5).integers(
      0, TOY["vocab_size"], n, dtype=np.int32)
  dec = SlotDecoder(cfg, 1)
  assert dec.padded_prefill and dec.ring_windows
  wide, narrow = (16, 8, 4), (4,)
  assert dec.plan(n, 0, wide) == [(16, 16)] * 4 + [(16, 11)]
  assert dec.plan(n, 0, narrow) == [(4, 4)] * 18 + [(4, 3)]
  want_cache, want_first = dec.prefill(params, prompt, narrow)
  cache, first = dec.prefill(params, prompt, wide)
  assert set(_cursors(cache)) == set(_cursors(want_cache)) == {n}
  for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(want_cache)):
    np.testing.assert_allclose(_written(a, n), _written(b, n), atol=1e-5,
                               rtol=1e-5)
  assert first == want_first
  want = trinity_family.reference_logits(
      trinity_family.make_weights(7, TOY), prompt[None], TOY)
  assert first == int(np.argmax(np.asarray(want)[0, -1]))
  assert _decode_from(dec, params, cache, first) \
      == _decode_from(dec, params, want_cache, want_first)


# -- a recurrent model pads too ------------------------------------------------


#: one period of Kimi-Linear's 3:1 pattern (KDA, KDA, KDA, MLA), layer 1
#: dense, toy widths: the keys of tests/test_kimi_linear.py's TOY
KIMI_TOY = dict(
    vocab_size=257, hidden_size=64, intermediate_size=96,
    num_hidden_layers=4, num_attention_heads=2, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=None,
    mla_use_nope=True, rms_norm_eps=1e-5, first_k_dense_replace=1,
    linear_attn_config=dict(full_attn_layers=[4], kda_layers=[1, 2, 3],
                            head_dim=16, num_heads=2,
                            short_conv_kernel_size=4),
    kda_low_rank_dim=8, moe_intermediate_size=32, num_experts=4,
    experts_first=4, num_experts_published=32, num_experts_per_token=4,
    num_shared_experts=1, routed_scaling_factor=2.446)


@pytest.fixture(scope="module")
def kimi_toy():
  cfg = fam.program_config(KIMI_TOY, 128, dtype=jnp.float32)
  return cfg, fam.program_params(7, KIMI_TOY), KIMI_TOY["vocab_size"]


def test_a_recurrent_model_takes_the_padded_plan(kimi_toy):
  """KDA state and convolution tail have no position axis for the cursor to
  mask; the layer takes the chunk's true length instead (models/kda.py), so
  such a config gets the ladder and the plan everyone gets: a 23-token
  prompt is ONE program of shape 32 with ``n_valid`` 23."""
  cfg, params, vocab = kimi_toy
  assert cfg.recurrent_state
  dec = SlotDecoder(cfg, 2)
  assert dec.padded_prefill and dec.buckets == DEFAULT_BUCKETS
  for n in (1, 67, 95, 100, 127):
    assert dec.plan(n) == padded_plan(n, 128)
  assert dec.plan(95) == [(128, 95)] and dec.plan(3) == [(16, 3)]
  calls, inner = [], dec._prefill_fn
  dec._prefill_fn = lambda *a: (calls.append(a), inner(*a))[1]
  prompt = np.random.default_rng(3).integers(0, vocab, 23, dtype=np.int32)
  acc = dict(prefill_chunks=0, prefill_tokens=0, prefill_padded_tokens=0,
             t_prefill_sync_s=0.0)
  cache, _ = dec.prefill(params, prompt, acc=acc)
  assert [(a[2].shape[1], int(a[3])) for a in calls] == [(32, 23)]
  assert (acc["prefill_chunks"], acc["prefill_tokens"],
          acc["prefill_padded_tokens"]) == (1, 32, 9)
  assert set(_cursors(cache)) == {23}


@pytest.fixture(scope="module")
def kimi_decoders(kimi_toy):
  """(the padded decoder, one steered to the exact plan, the reference's
  weights): one jit cache each for every case below."""
  exact = SlotDecoder(kimi_toy[0], 2)
  exact.padded_prefill = False              # the tests' own steering
  return SlotDecoder(kimi_toy[0], 2), exact, fam.make_weights(7, KIMI_TOY)


def _decode_many(dec, params, cache, first, slot=1, horizons=2):
  """``first`` and the tokens two ``step_many`` dispatches of horizon 4 emit
  for the row in ``slot`` of a two-slot slab whose other lane is free."""
  slabs = dec.insert(dec.init_slabs(), cache, slot)
  live = np.arange(2) == slot
  last, toks = np.where(live, first, 0).astype(np.int32), [first]
  left = np.where(live, 4 * horizons, 0).astype(np.int32)
  for _ in range(horizons):
    slabs, out, *_ = dec.step_many(params, slabs, last, left > 0, left, 4)
    out = np.asarray(out)
    toks.extend(int(t) for t in out[:, slot])
    last, left = out[-1], np.maximum(left - 4, 0)
  return toks


@pytest.mark.parametrize("n,buckets,plan", [
    (n, None, None) for n in (1, 2, 3, 5, 16, 17, 63, 64, 65, 100)] + [
        (21, (16, 8), [(16, 16), (8, 5)]),    # a whole chunk, then a padded
        (127, None, [(128, 127)]),            # the longest prompt of the row
        (5, (256,), [(1, 1)] * 5),    # no bucket fits the row: exact pieces
])
def test_recurrent_padded_prefill_equals_the_exact_plan(kimi_toy,
                                                        kimi_decoders, n,
                                                        buckets, plan):
  """The recurrent model's padded program against the steered exact
  decomposition of the same prompt: the same KDA state and convolution tail
  (whole: they have no positions), the same latent cache on [0, n), the
  cursor at n, the same first token; and ``step_many`` from the padded row
  emits the tokens it emits from the exact row, each the float32 reference's
  own first choice at its position (the full forward)."""
  cfg, params, vocab = kimi_toy
  dec, exact, weights = kimi_decoders
  prompt = np.random.default_rng(n).integers(0, vocab, n, dtype=np.int32)
  want_cache, want_first = exact.prefill(params, prompt, (64, 16, 4, 2, 1))
  if plan is not None:
    assert dec.plan(n, 0, buckets) == plan
  else:
    assert len(dec.plan(n)) == 1 and dec.plan(n)[0][1] == n
  cache, first = dec.prefill(params, prompt, buckets)
  assert set(_cursors(cache)) == set(_cursors(want_cache)) == {n}
  for (path, a), b in zip(tree_flatten_with_path(cache)[0],
                          jax.tree.leaves(want_cache)):
    whole = getattr(path[-1], "key", None) in ("kda_state", "conv_tail")
    np.testing.assert_allclose(
        np.asarray(a) if whole else _written(a, n),
        np.asarray(b) if whole else _written(b, n), atol=1e-4, rtol=1e-4)
  assert first == want_first
  toks = _decode_many(dec, params, cache, first)
  assert toks == _decode_many(exact, params, want_cache, want_first)
  if n + len(toks) <= cfg.max_seq_len:
    seq = np.concatenate([prompt, toks]).astype(np.int32)[None]
    z = np.asarray(fam.reference_logits(weights, seq, KIMI_TOY))[0]
    served = z[np.arange(n - 1, seq.shape[1] - 1), seq[0, n:]]
    assert float(np.max(z[n - 1:-1].max(axis=-1) - served)) < 1e-3


@pytest.mark.parametrize("model", ["attention", "recurrent"])
def test_engine_prefill_counters_add_up(model, kimi_toy):
  """``prefill_tokens`` = tokens the chunks computed, padding included;
  ``prefill_padded_tokens`` the padding among them; ``prefill_chunks`` the
  dispatches: real tokens = the prompts', whatever the model's cache kinds."""
  if model == "recurrent":
    cfg, params, vocab = kimi_toy
  else:
    cfg, vocab = _tiny(), 64
    params = tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=16).params
  rng = np.random.default_rng(11)
  prompts = [rng.integers(1, vocab, n, dtype=np.int32)
             for n in (3, 16, 17, 23, 33, 40)]
  with ServingEngine(params, cfg, num_slots=2, eos_id=None) as eng:
    assert eng.buckets == eng.decoder.buckets == DEFAULT_BUCKETS
    rids = [eng.submit(p, max_new_tokens=5) for p in prompts]
    for rid in rids:
      assert len(eng.result(rid, timeout=300)) > 0
  st = eng.stats
  plans = [eng.decoder.plan(len(p)) for p in prompts]
  assert st["prefills"] == len(prompts)
  assert st["prefill_chunks"] == sum(len(plan) for plan in plans)
  assert st["prefill_tokens"] == sum(s for plan in plans for s, _ in plan)
  assert st["prefill_tokens"] - st["prefill_padded_tokens"] \
      == sum(len(p) for p in prompts)
  if model == "recurrent":
    # max_seq_len 128: every prompt is one program
    assert st["prefill_padded_tokens"] == (16 - 3) + (32 - 17) + (32 - 23) \
        + (64 - 33) + (64 - 40)
    assert st["prefill_chunks"] == len(prompts)
  else:
    # max_seq_len 48: 33 and 40 find no bucket that ends inside the row
    assert st["prefill_padded_tokens"] == (16 - 3) + (32 - 17) + (32 - 23) \
        + (16 - 1) + (16 - 8)
    assert st["prefill_chunks"] == 4 + 2 + 2


# -- whom the true length does not touch ---------------------------------------


class _Untold(object):
  """A decoder's model whose ``apply`` is never told ``n_valid``: what
  ``Transformer.__call__`` traced before it took the argument."""

  def __init__(self, model):
    self.model = model

  def apply(self, *args, n_valid=None, **kw):
    return self.model.apply(*args, **kw)


def _sha(lowered):
  return hashlib.sha256(lowered.as_text().encode()).hexdigest()


def _prefill_sha(cfg, seg, told):
  from flax.core import meta
  dec = SlotDecoder(cfg, 2)
  params = jax.eval_shape(lambda: meta.unbox(dec.model.init(
      jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
  row = jax.eval_shape(lambda: tfm._zero_cache(dec.model, 1))
  if not told:
    dec.model = _Untold(dec.model)
  i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)        # noqa: E731
  return _sha(jax.jit(dec._prefill_impl).lower(params, row, i32(1, seg),
                                               i32()))


def _plain_cfg(model):
  if model == "trinity":
    from test_trinity import MAX_SEQ, TOY
    return trinity_family.program_config(TOY, MAX_SEQ)
  looped = dict(loop_passes=4, norm="rms") if model == "ouro" else {}
  return _tiny(dtype=jnp.bfloat16, max_seq_len=512, **looped)


@pytest.mark.parametrize("model", ["gpt2", "ouro", "trinity"])
def test_the_true_length_changes_no_program_of_a_positional_model(model):
  """The padded prefill program of a model whose every cache leaf has a
  position axis (plain attention, a looped model's cache a pass, window and
  full layers) lowers to the same text whether ``Transformer.__call__`` is
  handed the chunk's true length or not: only a "kda" mixer reads it."""
  cfg = _plain_cfg(model)
  assert not cfg.recurrent_state
  assert _prefill_sha(cfg, 32, told=True) == _prefill_sha(cfg, 32, told=False)


def test_the_true_length_reaches_the_recurrent_chunk_and_not_the_step(
    kimi_toy):
  """The recurrent model: the mask IS in a padded chunk's program (told and
  untold differ), and is in no one-token program: ``step_many``'s model
  call lowers to the same text with ``n_valid`` handed in as without (the
  ``seg == 1`` form takes its one token as real)."""
  cfg = kimi_toy[0]
  assert _prefill_sha(cfg, 32, told=True) != _prefill_sha(cfg, 32, told=False)
  dec = SlotDecoder(cfg, 2)
  slabs = jax.eval_shape(dec.init_slabs)
  params = jax.eval_shape(lambda: kimi_toy[1])

  def step(told):
    def one(params, slabs, tok, n):
      return dec.slab_model.apply(
          {"params": params, "cache": slabs}, tok[:, None], decode=True,
          mutable=["cache"], **(dict(n_valid=n) if told else {}))
    return _sha(jax.jit(one).lower(
        params, slabs, jax.ShapeDtypeStruct((2,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)))

  assert step(True) == step(False)
