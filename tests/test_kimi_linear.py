"""Kimi-Linear's three layer kinds (models/kda.py, models/mla.py,
models/experts.py + parallel/expert_parallel.py) and the whole model through
the serving slab, against the plain reference in ``kimi_linear_family.py`` (a
byte-for-byte copy of ``benchmarks/families/kimi_linear.py``: float32, no
cache, no chunks, none of the program's code). Seeded weights, toy widths,
CPU.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kimi_linear_family as fam
from tensorflowonspark_tpu import serving
from tensorflowonspark_tpu.models import kda
from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.parallel import expert_parallel as ep
from tensorflowonspark_tpu.serving.slots import SlotDecoder

#: two periods of the published 3:1 pattern, layer 1 dense, toy widths
TOY = dict(
    vocab_size=257, hidden_size=64, intermediate_size=96,
    num_hidden_layers=8, num_attention_heads=2, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=None,
    mla_use_nope=True, rms_norm_eps=1e-5, first_k_dense_replace=1,
    linear_attn_config=dict(full_attn_layers=[4, 8],
                            kda_layers=[1, 2, 3, 5, 6, 7], head_dim=16,
                            num_heads=2, short_conv_kernel_size=4),
    kda_low_rank_dim=8, moe_intermediate_size=32, num_experts=4,
    experts_first=4, num_experts_published=32, num_experts_per_token=4,
    num_shared_experts=1, routed_scaling_factor=2.446)
MAX_SEQ = 128


@pytest.fixture(scope="module")
def toy():
  cfg = fam.program_config(TOY, MAX_SEQ, dtype=jnp.float32)
  return dict(cfg=cfg, weights=fam.make_weights(7, TOY),
              params=fam.program_params(7, TOY))


def _tokens(seed, *shape):
  return np.random.default_rng(seed).integers(0, TOY["vocab_size"], shape,
                                              dtype=np.int32)


# -- KDA ----------------------------------------------------------------------


def _kda_inputs(seed, b, seg, h=2, dk=16):
  ks = jax.random.split(jax.random.PRNGKey(seed), 6)
  unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
  q = unit(jax.random.normal(ks[0], (b, seg, h, dk))) * dk ** -0.5
  k = unit(jax.random.normal(ks[1], (b, seg, h, dk)))
  v = jax.random.normal(ks[2], (b, seg, h, dk))
  # decays from 0.007 a step (a channel dead within the block) to 0.9999
  la = -jnp.exp(jax.random.uniform(ks[3], (b, seg, h, dk), minval=-9.0,
                                   maxval=1.6))
  beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, seg, h)))
  s0 = jax.random.normal(ks[5], (b, h, dk, dk))
  return s0, q, k, v, la, beta


def _per_token(s0, q, k, v, la, beta):
  outs = []
  for t in range(q.shape[1]):
    s0, o = kda.recurrent_step(s0, q[:, t], k[:, t], v[:, t],
                               jnp.exp(la[:, t]), beta[:, t])
    outs.append(o)
  return s0, jnp.stack(outs, axis=1)


@pytest.mark.parametrize("seg", [1, 5, 64, 100, 192])
def test_kda_chunkwise_equals_the_per_token_recurrence(seg):
  """One call of the chunkwise form (one block, a padded block, whole
  blocks, blocks plus a padded one) leaves the state and gives the outputs
  of the recurrence, from a non-zero incoming state and with channels that
  die inside a block."""
  args = _kda_inputs(seg, 2, seg)
  want_s, want_o = _per_token(*args)
  got_s, got_o = jax.jit(kda.chunk_rule)(*args)
  np.testing.assert_allclose(got_o, want_o, atol=2e-5, rtol=2e-5)
  np.testing.assert_allclose(got_s, want_s, atol=2e-5, rtol=2e-5)


def test_kda_one_step_is_the_written_recurrence():
  """``recurrent_step`` reads both outputs in one pass (o = S'^T q + (k.q)
  b u); the equations as ISSUE 26 writes them, literally."""
  s0, *rest = _kda_inputs(3, 2, 1)
  q, k, v, la, beta = (x[:, 0] for x in rest)
  a = jnp.exp(la)
  decayed = a[..., None] * s0
  u = v - jnp.einsum("bhkv,bhk->bhv", decayed, k)
  want_s = decayed + beta[..., None, None] * k[..., None] * u[:, :, None]
  want_o = jnp.einsum("bhkv,bhk->bhv", want_s, q)
  got_s, got_o = kda.recurrent_step(s0, q, k, v, a, beta)
  np.testing.assert_allclose(got_s, want_s, atol=1e-6)
  np.testing.assert_allclose(got_o, want_o, atol=1e-6)


def _layer_module(toy, kind):
  """(module, params, reference weights of that layer) for the first layer
  of ``kind`` in the toy model."""
  i = toy["cfg"].layer_types.index(kind)
  z = fam.sizes(TOY)
  _, w = fam._layer_weights(toy["weights"], z, i)
  return toy["params"]["layer_%d" % i][kind], w, z


def _zero_layer_cache(mod, x):
  """The all-zeros ``cache`` collection of a mixer module for batch
  ``x.shape[0]``."""
  return jax.tree.map(
      lambda s: jnp.zeros(s.shape, s.dtype),
      jax.eval_shape(lambda: mod.init(jax.random.PRNGKey(0), x[:, :1],
                                      decode=True)["cache"]))


def test_kda_layer_over_calls_of_32_4_1_carries_state_and_tail(toy):
  """The module through its cache in calls of 32 + 4 + 1 tokens (chunkwise,
  chunkwise, recurrent) equals the reference's per-token recurrence over the
  37, and leaves the state and convolution tail one call over all 37 leaves:
  both are carried between calls."""
  params, w, z = _layer_module(toy, "kda")
  mod = kda.KDA(toy["cfg"])
  x = jax.random.normal(jax.random.PRNGKey(4), (2, 37, TOY["hidden_size"]))
  want = fam._kda(x, w, z, "f32")

  def through_cache(pieces):
    cache = _zero_layer_cache(mod, x)
    outs, off = [], 0
    for n in pieces:
      y, mut = step(cache, x[:, off:off + n])
      cache, off = mut["cache"], off + n
      outs.append(y)
    return jnp.concatenate(outs, axis=1), cache

  step = jax.jit(lambda c, t: mod.apply(
      {"params": params, "cache": c}, t, decode=True, mutable=["cache"]))

  got, cache = through_cache((32, 4, 1))
  np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
  np.testing.assert_allclose(
      jax.jit(lambda t: mod.apply({"params": params}, t))(x), want,
      atol=2e-5, rtol=2e-5)
  _, whole = through_cache((37,))
  assert set(cache) == {"kda_state", "conv_tail"}
  for name in cache:
    np.testing.assert_allclose(cache[name], whole[name], atol=2e-5,
                               rtol=2e-5)
  assert float(jnp.max(jnp.abs(cache["kda_state"]))) > 1e-3


def _bucket(n):
  return min(b for b in serving.DEFAULT_BUCKETS if b >= n)


@pytest.mark.parametrize("warm", [False, True], ids=["fresh", "warm"])
@pytest.mark.parametrize("n_valid", [1, 2, 3, 5, 16, 17, 63, 64, 65, 100])
def test_kda_layer_masks_a_padded_tail_out_of_state_and_tail(toy, n_valid,
                                                             warm):
  """A chunk padded up to its prefill bucket (16 .. 128; the padding is
  noise as large as the tokens) with ``n_valid`` real tokens leaves the
  ``kda_state`` and ``conv_tail``, and gives the outputs at the real
  positions, of the unpadded call on those tokens alone: a padded token
  neither decays nor writes, and the tail is taken at the true length. Fewer
  real tokens than the tail is long (1, 2 of 3) keep rows of the tail the
  cache held, a WARM one (7 tokens went before) included; one real token is
  checked against the recurrent form, 17 .. 100 against other blocks."""
  params, _, _ = _layer_module(toy, "kda")
  mod = kda.KDA(toy["cfg"])
  seg = _bucket(n_valid)
  x = jax.random.normal(jax.random.PRNGKey(n_valid),
                        (2, 7 + seg, TOY["hidden_size"]))
  apply = jax.jit(lambda c, t, n=None: mod.apply(
      {"params": params, "cache": c}, t, decode=True, n_valid=n,
      mutable=["cache"]))
  cache = _zero_layer_cache(mod, x)
  if warm:
    cache = apply(cache, x[:, :7])[1]["cache"]
    assert float(jnp.max(jnp.abs(cache["conv_tail"]))) > 1e-3
  chunk = x[:, 7:]
  want_y, want = apply(cache, chunk[:, :n_valid])
  got_y, got = apply(cache, chunk, jnp.int32(n_valid))
  np.testing.assert_allclose(got_y[:, :n_valid], want_y, atol=2e-5, rtol=2e-5)
  for name in ("kda_state", "conv_tail"):
    np.testing.assert_allclose(got["cache"][name], want["cache"][name],
                               atol=2e-5, rtol=2e-5)
  # the mask is what does it: unmasked, the padding is integrated
  if n_valid < seg:
    _, loose = apply(cache, chunk)
    assert float(jnp.max(jnp.abs(
        loose["cache"]["kda_state"] - want["cache"]["kda_state"]))) > 1e-3


# -- MLA ----------------------------------------------------------------------


def test_mla_absorbed_decode_through_the_cache_equals_the_full_forward(toy):
  """A wide chunk (keys and values expanded: 80 x 2 heads > 128 query rows),
  a narrow chunk from a warm cache and single tokens (absorbed against the
  latent cache) equal the reference's full forward; the cache holds the
  latent, padded to whole lane tiles, and nothing per head."""
  from tensorflowonspark_tpu.models import mla
  params, w, z = _layer_module(toy, "mla")
  mod = mla.MLA(toy["cfg"])
  x = jax.random.normal(jax.random.PRNGKey(5), (2, 100, TOY["hidden_size"]))
  want = fam._mla(x, w, z, "f32")
  np.testing.assert_allclose(
      jax.jit(lambda t: mod.apply({"params": params}, t))(x), want,
      atol=2e-5, rtol=2e-5)
  step = jax.jit(lambda c, t: mod.apply(
      {"params": params, "cache": c}, t, decode=True, mutable=["cache"]))
  cache = _zero_layer_cache(mod, x)
  assert cache["cached_kv"].shape == (2, MAX_SEQ, 128)    # 32 + 8 -> 128
  outs, off = [], 0
  for n in (80, 16, 1, 1, 2):
    y, mut = step(cache, x[:, off:off + n])
    cache, off = mut["cache"], off + n
    outs.append(y)
  np.testing.assert_allclose(jnp.concatenate(outs, axis=1), want, atol=2e-5,
                             rtol=2e-5)
  assert int(cache["index"]) == 100


# -- experts ------------------------------------------------------------------


def _expert_layer(toy, held, first, seed=0):
  """The toy model's first expert layer with ``held`` experts from
  ``first``: the reference's weights (a fresh, wider expert stack)."""
  z = dict(fam.sizes(TOY), held=held, first=first)
  _, w = fam._layer_weights(toy["weights"], fam.sizes(TOY), 1)
  ks = jax.random.split(jax.random.PRNGKey(seed), 3)
  d, f = z["d_model"], z["expert_ff"]
  full = dict(exp_gate=jax.random.normal(ks[0], (32, d, f)) * d ** -0.5,
              exp_up=jax.random.normal(ks[1], (32, d, f)) * d ** -0.5,
              exp_down=jax.random.normal(ks[2], (32, f, d)) * f ** -0.5)
  w.update({n: v[first:first + held] for n, v in full.items()})
  return w, z


def _routed_part(x, w, z):
  """The program's routed part for this share of the experts."""
  flat = x.reshape(-1, x.shape[-1])
  experts, weights = ep.route_sigmoid_topk(
      flat, w["router"], w["router_bias"], z["top_k"], z["scale"])
  y, held = ep.held_experts_ffn(flat, experts, weights, w["exp_gate"],
                                w["exp_up"], w["exp_down"], z["first"])
  return y.reshape(x.shape), held


def test_grouped_experts_drop_no_token_when_all_choose_one_expert(toy):
  """Every token's first choice forced to ONE held expert (a router bias):
  its group is all the tokens, the other groups are ragged or empty, and the
  grouped product equals the dense form (every token through every held
  expert, weighted) row for row: no capacity, nothing dropped."""
  w, z = _expert_layer(toy, held=8, first=8)
  w["router_bias"] = w["router_bias"].at[10].set(5.0)
  x = jax.random.normal(jax.random.PRNGKey(6), (3, 50, TOY["hidden_size"]))
  got, held = jax.jit(lambda x: _routed_part(x, w, z))(x)
  want = fam._experts(x, w, dict(z, shared=0), "f32")
  np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
  experts, _ = fam.route(x, w, z)
  assert bool(jnp.all(jnp.any(experts == 10, axis=-1)))       # all 150 tokens
  counts = np.bincount(np.asarray(experts).ravel(), minlength=32)[8:16]
  assert counts[2] == 150 and counts.min() < 40 and int(held.sum()) \
      == counts.sum()


def test_the_sixteen_shares_add_up_to_the_uncut_layer(toy):
  """The sizing guide's share test: 32 experts over 16 chips, 2 each. The
  routed parts the 16 shares compute, plus the shared expert counted ONCE,
  equal the uncut reference layer (all 32 held); every token's 4 assignments
  are computed by exactly one share each."""
  x = jax.random.normal(jax.random.PRNGKey(8), (2, 24, TOY["hidden_size"]))
  w_all, z_all = _expert_layer(toy, held=32, first=0)
  want = fam._experts(x, w_all, z_all, "f32")
  total, assigned = 0.0, 0
  for share in range(16):
    w, z = _expert_layer(toy, held=2, first=2 * share)
    y, held = _routed_part(x, w, z)
    total, assigned = total + y, assigned + int(held.sum())
  shared = fam._swiglu(x, w_all["shared_gate"], w_all["shared_up"],
                       w_all["shared_down"], "f32")
  np.testing.assert_allclose(total + shared, want, atol=3e-5, rtol=3e-5)
  assert assigned == 2 * 24 * TOY["num_experts_per_token"]


# -- the whole model ----------------------------------------------------------


def test_forward_and_chunked_decode_equal_the_reference(toy):
  """The program's tree is the family's; the plain forward, and prefill in
  chunks then single tokens through the cache, give the reference's logits."""
  cfg, params = toy["cfg"], toy["params"]
  model = tfm.Transformer(cfg)
  from flax.core import meta
  own = meta.unbox(jax.eval_shape(
      lambda: model.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))["params"]))
  assert jax.tree.structure(own) == jax.tree.structure(params)
  assert [a.shape for a in jax.tree.leaves(own)] \
      == [a.shape for a in jax.tree.leaves(params)]
  toks = _tokens(1, 2, 38)
  want = fam.reference_logits(toy["weights"], toks, TOY)
  np.testing.assert_allclose(
      jax.jit(lambda t: model.apply({"params": params}, t))(toks), want,
      atol=5e-4)
  step = jax.jit(lambda c, t: model.apply(
      {"params": params, "cache": c}, t, decode=True, mutable=["cache"]))
  cache, outs, off = tfm._zero_cache(model, 2), [], 0
  for n in (32, 4, 1, 1):
    lg, mut = step(cache, toks[:, off:off + n])
    cache, off = mut["cache"], off + n
    outs.append(lg)
  np.testing.assert_allclose(jnp.concatenate(outs, axis=1), want, atol=5e-4)


def test_slot_decoder_serves_the_model_with_a_stopped_lane_and_a_reused_slot(
    toy):
  """Prefill (bucketed chunks), ``insert`` and ``step_many`` over the one
  slab: 3 requests on 2 slots, horizon 4. Request 1 stops mid-horizon (its
  lane runs on frozen, integrating garbage into its KDA state), request 2
  then takes that slot (``insert`` overwrites state, tail and latent rows).
  Every request's tokens equal its own ``greedy_generate_kv`` decode, and
  every served token is the reference's own first choice to 1e-3."""
  cfg, params = toy["cfg"], toy["params"]
  dec = SlotDecoder(cfg, 2)
  assert dec.counted
  slabs = dec.init_slabs()
  assert {leaf.shape[0] for leaf in jax.tree.leaves(slabs)} == {2}
  # requests 0 and 2 share a shape (one greedy_generate_kv compile)
  prompts = [_tokens(20 + i, n) for i, n in enumerate((21, 9, 21))]
  budgets = [12, 6, 12]                      # request 1: 1 + 5 of a horizon
  want = [np.asarray(tfm.greedy_generate_kv(
      params, cfg, jnp.asarray(p)[None], m))[0, len(p):]
          for p, m in zip(prompts, budgets)]
  got = [[] for _ in prompts]
  slot_of, last, left = {}, np.zeros(2, np.int32), np.zeros(2, np.int32)

  def admit(i, slot):
    nonlocal slabs
    row, first = dec.prefill(params, prompts[i], (16, 4, 1))
    slabs = dec.insert(slabs, row, slot)
    slot_of[slot], last[slot], left[slot] = i, first, budgets[i] - 1
    got[i].append(first)

  admit(0, 0)
  admit(1, 1)
  totals = dict(held=0, touched=0, context=0)
  for _ in range(5):
    active = left > 0
    slabs, toks, _, _, counts = dec.step_many(params, slabs, last, active,
                                              left, 4)
    toks = np.asarray(toks)
    for name in totals:
      totals[name] += int(counts[name])
    for slot, i in list(slot_of.items()):
      n = int(min(4, left[slot]))
      got[i].extend(toks[:n, slot])
      left[slot] -= n
      last[slot] = toks[n - 1, slot] if n else last[slot]
      if left[slot] == 0 and i == 1:
        admit(2, slot)                       # the stopped lane's slot, reused
  for i in range(3):
    np.testing.assert_array_equal(np.asarray(got[i]), want[i])
    seq = np.concatenate([prompts[i], want[i]])[None]
    z = np.asarray(fam.reference_logits(toy["weights"], seq, TOY))[0]
    n = len(prompts[i])
    served = z[np.arange(n - 1, len(seq[0]) - 1), seq[0, n:]]
    assert float(np.max(z[n - 1:-1].max(axis=-1) - served)) < 1e-3
  # counters are sums over LIVE lanes: 24 decoded tokens, 7 expert layers
  live = sum(budgets) - 3
  assert 0 < totals["held"] <= live * 7 * TOY["num_experts_per_token"]
  assert 0 < totals["touched"] <= totals["held"]
  want_context = sum(len(p) + j for p, m in zip(prompts, budgets)
                     for j in range(m - 1))
  assert totals["context"] == want_context


def test_counters_leave_frozen_lanes_out(toy):
  """The same step with one lane frozen counts that lane's assignments,
  experts and context out, and a frozen lane emits pad."""
  cfg, params = toy["cfg"], toy["params"]
  dec = SlotDecoder(cfg, 2)
  rows = [dec.prefill(params, _tokens(40 + i, 11 + i)) for i in range(2)]

  def step(active):
    slabs = dec.init_slabs()
    for slot, (row, _) in enumerate(rows):
      slabs = dec.insert(slabs, row, slot)
    out = dec.step_many(params, slabs, [r[1] for r in rows], active, [3, 3],
                        2)
    return np.asarray(out[1]), {k: int(v) for k, v in out[4].items()}

  both, lane0, lane1 = (step(a) for a in ([True, True], [True, False],
                                          [False, True]))
  for name in ("held", "context"):
    assert both[1][name] == lane0[1][name] + lane1[1][name]
  assert lane0[1]["context"] == 11 + 12
  assert max(lane0[1]["touched"], lane1[1]["touched"]) \
      <= both[1]["touched"] <= lane0[1]["touched"] + lane1[1]["touched"]
  assert (lane0[0][:, 1] == dec.pad_id).all()
  np.testing.assert_array_equal(lane0[0][:, 0], both[0][:, 0])


def test_act_f32_keeps_float32_activations_against_bf16_weights():
  """bf16-STORED weights, 4 KDA layers with experts (no MLA: its latent
  cache is bf16 by design). With ``act_f32`` every activation meets the
  bf16 matrices unrounded (three exact bf16 passes) and the stream, state
  and convolution tail are float32: prefill chunks and decode steps through
  the cache follow the float32 reference to 2e-3; without it (an
  activation rounded to bf16 at every product) the same model is over ten
  times further off, an expert or two having fallen the other way."""
  conf = dict(TOY, num_hidden_layers=4, linear_attn_config=dict(
      TOY["linear_attn_config"], full_attn_layers=[],
      kda_layers=[1, 2, 3, 4]))
  weights = fam.make_weights(9, conf, "bfloat16")
  params = fam.program_params(9, conf, "bfloat16")
  toks = _tokens(9, 4, 41)
  want = fam.reference_logits(weights, toks, conf)
  worst = {}
  for f32 in (False, True):
    cfg = fam.program_config(conf, 64, act_f32=f32)
    assert cfg.dtype == jnp.bfloat16
    model = tfm.Transformer(cfg)
    step = jax.jit(lambda c, t: model.apply(
        {"params": params, "cache": c}, t, decode=True, mutable=["cache"]))
    cache, outs, off = tfm._zero_cache(model, 4), [], 0
    tail = cache["layer_0"]["kda"]["conv_tail"].dtype
    assert tail == (jnp.float32 if f32 else jnp.bfloat16)
    for n in (32, 4, 1, 1, 1, 1, 1):
      lg, mut = step(cache, toks[:, off:off + n])
      cache, off = mut["cache"], off + n
      outs.append(lg)
    worst[f32] = float(jnp.max(jnp.abs(
        jnp.concatenate(outs, axis=1) - want)))
  assert worst[True] < 2e-3 and worst[False] > 10 * worst[True], worst


# -- what must refuse this model ----------------------------------------------


@pytest.mark.parametrize("kwargs, mechanism", [
    (dict(page_size=16), "paged KV pool"),
    (dict(page_size=16, prefix_pages=4), "shared-prefix cache"),
    (dict(spec_depth=2), "speculative decoding"),
])
def test_engine_refuses_what_assumes_keys_and_values_by_position(
    toy, kwargs, mechanism):
  """Pages, prefix reuse and cursor rollback assume K/V per head at a
  position in every layer; a model with KDA layers is refused at
  construction, by the mechanism's name, and never served corrupted."""
  with pytest.raises(ValueError, match=mechanism):
    serving.ServingEngine(toy["params"], toy["cfg"], num_slots=2, **kwargs)
  decoder_kwargs = {k: v for k, v in kwargs.items() if k != "prefix_pages"}
  if "prefix_pages" not in kwargs:
    with pytest.raises(ValueError, match=mechanism):
      SlotDecoder(toy["cfg"], 2, **decoder_kwargs)


def test_paged_config_refuses_layers_without_kv(toy):
  with pytest.raises(ValueError, match="kda/mla"):
    dataclasses.replace(toy["cfg"], kv_page_size=16, kv_num_pages=8,
                        kv_pages_per_slot=4)


# -- the per-layer block's other knobs ----------------------------------------


def test_head_dim_is_its_own_number():
  """``attn_head_dim`` decouples the attention head size from d_model /
  num_heads (ROADMAP R1): projections, cache and decode follow it."""
  cfg = tfm.TransformerConfig(vocab_size=61, num_layers=2, num_heads=2,
                              d_model=32, d_ff=64, max_seq_len=32,
                              dtype=jnp.float32, remat=False,
                              attn_head_dim=24, norm="rms", mlp_act="swiglu",
                              tie_embeddings=False)
  assert cfg.head_dim == 24
  model = tfm.Transformer(cfg)
  toks = jnp.asarray(_tokens(2, 2, 12) % 61)
  from flax.core import meta
  params = meta.unbox(jax.jit(model.init)(jax.random.PRNGKey(0),
                                          toks)["params"])
  assert params["layer_0"]["attn"]["q"]["kernel"].shape == (32, 2, 24)
  assert set(params["layer_0"]["mlp"]) == {"gate", "up", "down"}
  assert params["head"]["kernel"].shape == (32, 61)
  full = jax.jit(lambda t: model.apply({"params": params}, t))(toks)
  cache = tfm._zero_cache(model, 2)
  assert cache["layer_0"]["attn"]["cached_k"].shape == (2, 32, 48)
  step = jax.jit(lambda c, t: model.apply(
      {"params": params, "cache": c}, t, decode=True, mutable=["cache"]))
  lg, mut = step(cache, toks[:, :8])
  lg2, _ = step(mut["cache"], toks[:, 8:])
  np.testing.assert_allclose(jnp.concatenate([lg, lg2], axis=1), full,
                             atol=1e-4)


def test_layer_types_are_checked():
  with pytest.raises(ValueError, match="layer_types"):
    tfm.TransformerConfig(num_layers=2, layer_types=("kda",))
  with pytest.raises(ValueError, match="ffn_types"):
    tfm.TransformerConfig(num_layers=1, ffn_types=("dense",))
  with pytest.raises(ValueError, match="held experts"):
    tfm.TransformerConfig(num_layers=1, ffn_types=("experts",),
                          experts_total=8, experts_held=4, experts_first=6)
