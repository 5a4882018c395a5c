"""The ``deepseek_v3`` family: how a ``deepseek_v3`` configuration file
(deepseek-ai's DeepSeek-V3) becomes (a) the benchmark's own plain reference
and (b) the program's ``TransformerConfig`` and parameter tree.

Two halves, kept apart on purpose (as ``families/gpt2.py``):

* **the reference half** (``make_weights``, ``reference_logits``,
  ``reference_layer``, ``route``) imports nothing of the program. It is the
  model in straightforward ``jax.numpy``, a FULL forward pass with no cache
  and nothing absorbed: ``x = E[tokens]``; a layer is ``x += Attn(RMSNorm(x));
  x += FFN(RMSNorm(x))``; final RMSNorm, untied head, no bias anywhere.
  Attention, token ``t``, head ``i``: ``cq = RMSNorm(W_qa x)``; ``[q_nope_i ;
  q_rope_i] = W_qb,i cq``; ``[ckv ; kr] = W_kva x``; ``c = RMSNorm(ckv)``;
  ``[k_nope_i ; v_i] = W_kvb,i c``; ``q_rope_i`` and the SHARED ``kr`` rotate
  at position ``t`` (half-split pairs ``(j, j + rope/2)``) at YaRN's
  frequencies (``yarn_frequencies``); ``k_i = [k_nope_i ; rot(kr)]``; ``p =
  softmax_{j<=t}(q_i . k_j x (nope + rope)^-0.5 x m^2)`` with ``m = 0.1 x
  mscale_all_dim x ln(factor) + 1``; ``o = W_o [p v]``. The feed-forward is a
  dense gated-SiLU MLP (the first ``first_k_dense_replace`` layers) or sparse
  experts: ``s = sigmoid(y W_r)`` in float32; ``c = s + b``; the ``n_group``
  groups of consecutive experts are scored by the sum of their two largest
  ``c``, the ``topk_group`` best groups stay and the others' experts cannot be
  chosen; the ``k`` largest ``c`` left are chosen; weights ``s_e / (sum of the
  chosen s + 1e-20) x routed_scaling_factor``; plus ``n_shared_experts``
  shared experts every token passes. float32 with
  ``jax.default_matmul_precision("highest")``; attention runs a group of
  heads at a time and inside it a block of queries at a time, a feed-forward a
  block of tokens at a time, and the held experts are upcast and applied ONE AT
  A TIME, so that 16384 positions x 128 heads fit beside the bf16 weights.
  ``precision`` swaps the matrix multiplications' inputs to a lower precision
  (``"fp8"``), leaves the router's group limit out (``"no_group"``) or leaves
  ``m^2`` off the softmax scale (``"no_mscale"``): the CONTROLS of the
  ``correct`` decision, never a speed-up.
* **the program half** (``program_config``, ``program_params``) is the only
  place that touches ``tensorflowonspark_tpu``.

The configuration is ONE CHIP'S SHARE of an expert-parallel deployment and a
CUT IN DEPTH: ``n_routed_experts`` counts the experts HELD here
(``experts_first`` the first of them), ``n_routed_experts_published`` is the
router's width, over which the groups are laid. The reference gets the same
share: an assignment to an expert held elsewhere adds nothing, in both. The
multi-token-prediction block (``num_nextn_predict_layers``) is LEFT OUT and
the file has to say so (``multi_token_prediction``). ``tests/
deepseek_v3_family.py`` is a byte-for-byte copy of this file
(``benchmarks/tests/test_deepseek_v3.py`` keeps them equal), so that tier-1
tests need nothing of ``benchmarks/``.
"""

import math

EMBED_STD = 0.02
ROUTE_EPS = 1e-20
#: heads a group and queries a block of the reference's attention (scores of
#: 16 heads x 256 x 16384 keys are 0.27 GB in float32)
HEAD_GROUP = 16
QUERY_BLOCK = 256
#: tokens a block of the reference's feed-forward
TOKEN_BLOCK = 2048
PRECISIONS = ("f32", "bf16", "fp8", "no_group", "no_mscale")


def sizes(config: dict) -> dict:
  """The model's sizes from a ``config.json``-style dict (Hugging Face
  ``deepseek_v3`` key names, plus the cut's keys)."""
  rs = config["rope_scaling"]
  z = dict(
      vocab=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
      layers=int(config["num_hidden_layers"]),
      dense_layers=int(config["first_k_dense_replace"]),
      eps=float(config["rms_norm_eps"]),
      heads=int(config["num_attention_heads"]),
      q_rank=int(config["q_lora_rank"]), kv_rank=int(config["kv_lora_rank"]),
      nope=int(config["qk_nope_head_dim"]),
      rope=int(config["qk_rope_head_dim"]), v_dim=int(config["v_head_dim"]),
      theta=float(config["rope_theta"]), yarn_factor=float(rs["factor"]),
      yarn_original=int(rs["original_max_position_embeddings"]),
      yarn_fast=float(rs["beta_fast"]), yarn_slow=float(rs["beta_slow"]),
      yarn_all_dim=float(rs["mscale_all_dim"]),
      d_ff=int(config["intermediate_size"]),
      expert_ff=int(config["moe_intermediate_size"]),
      held=int(config["n_routed_experts"]),
      first=int(config.get("experts_first", 0)),
      routed=int(config.get("n_routed_experts_published",
                            config["n_routed_experts"])),
      top_k=int(config["num_experts_per_tok"]),
      shared=int(config["n_shared_experts"] or 0),
      scale=float(config["routed_scaling_factor"]),
      groups=int(config["n_group"]), groups_kept=int(config["topk_group"]))
  if rs.get("type") != "yarn" or float(rs["mscale"]) != z["yarn_all_dim"]:
    raise ValueError("this family's rotary is YaRN with mscale == "
                     "mscale_all_dim (cos and sin are then multiplied by 1)")
  if config.get("scoring_func") != "sigmoid" \
      or not config.get("norm_topk_prob", True) \
      or config.get("topk_method") != "noaux_tc" \
      or config.get("moe_layer_freq", 1) != 1:
    raise ValueError("this family's router is a sigmoid with a selection "
                     "bias and renormalised weights, in every layer after "
                     "the dense ones")
  if config.get("hidden_act", "silu") != "silu" \
      or config.get("tie_word_embeddings", False) \
      or config.get("attention_bias", False):
    raise ValueError("this family's MLP is gated SiLU, its head untied and "
                     "its projections without bias")
  if config.get("num_key_value_heads", z["heads"]) != z["heads"]:
    raise ValueError("latent attention gives every query head its own key")
  if config.get("num_nextn_predict_layers") \
      and config.get("multi_token_prediction") != "left out":
    raise ValueError(
        "the multi-token-prediction block (num_nextn_predict_layers=%r) is "
        "not built: a draft that is a trained extra block reading the "
        "target's last hidden state does not exist; a configuration says "
        '"multi_token_prediction": "left out"'
        % config["num_nextn_predict_layers"])
  per = z["routed"] // z["groups"]
  if z["rope"] % 2 or z["routed"] % z["groups"] or per < 2 \
      or not 0 < z["groups_kept"] <= z["groups"] \
      or z["top_k"] > z["groups_kept"] * per \
      or not 0 < z["dense_layers"] <= z["layers"]:
    raise ValueError("the rotated part of a head is whole pairs, the groups "
                     "divide the router's width into two experts or more "
                     "and leave room for the chosen ones, and a dense layer "
                     "leads")
  return z


def _leaves(z):
  """``(name, shape, kind)``: ``kind`` an int = fan-in of a normal matrix,
  or the name of a special draw. Matrices onto heads are stored FLAT (heads x
  width in the last axis), as the published checkpoints store them."""
  d, n, h = z["d_model"], z["layers"], z["heads"]
  nd, ne = z["dense_layers"], z["layers"] - z["dense_layers"]
  qr, r, dn, dr, dv = (z["q_rank"], z["kv_rank"], z["nope"], z["rope"],
                       z["v_dim"])
  f, fs = z["expert_ff"], z["shared"] * z["expert_ff"]
  return (
      ("embed", (z["vocab"], d), "embed"), ("head", (d, z["vocab"]), d),
      ("ln_f", (d,), "ones"), ("ln1", (n, d), "ones"), ("ln2", (n, d), "ones"),
      ("q_a", (n, d, qr), d), ("q_norm", (n, qr), "ones"),
      ("q_b", (n, qr, h * (dn + dr)), qr),
      ("kva", (n, d, r + dr), d), ("kv_norm", (n, r), "ones"),
      ("kvb", (n, r, h * (dn + dv)), r), ("wo", (n, h * dv, d), h * dv),
      ("mlp_gate", (nd, d, z["d_ff"]), d), ("mlp_up", (nd, d, z["d_ff"]), d),
      ("mlp_down", (nd, z["d_ff"], d), z["d_ff"]),
      ("router", (ne, d, z["routed"]), "router"),
      ("router_bias", (ne, z["routed"]), "router_bias"),
      ("exp_gate", (ne, z["held"], d, f), d),
      ("exp_up", (ne, z["held"], d, f), d),
      ("exp_down", (ne, z["held"], f, d), f),
      ("shared_gate", (ne, d, fs), d), ("shared_up", (ne, d, fs), d),
      ("shared_down", (ne, fs, d), fs))


def param_count(config: dict) -> int:
  z = sizes(config)
  return sum(math.prod(shape) for _, shape, _ in _leaves(z))


#: leaves kept in float32 whatever the matrices are stored in: norm scales and
#: the router (its scores decide a top-k)
_F32 = ("ones", "router", "router_bias")


def _weights_impl(key, z, dtype):
  import jax
  import jax.numpy as jnp
  out = {}
  for i, (name, shape, kind) in enumerate(_leaves(z)):
    k = jax.random.fold_in(key, i)
    if kind == "ones":
      w = jnp.ones(shape, jnp.float32)
    elif kind == "embed":
      w = jax.random.normal(k, shape, jnp.float32) * EMBED_STD
    elif kind == "router":
      w = jax.random.normal(k, shape, jnp.float32) / math.sqrt(shape[1])
    elif kind == "router_bias":
      w = jax.random.normal(k, shape, jnp.float32) * 0.02
    elif len(shape) > 2:
      # a stack over layers (and experts), drawn and rounded a matrix at a
      # time: drawn whole, the float32 normals of an expert stack are GBs of
      # scratch that the process then counts at its peak
      lead = math.prod(shape[:-2])
      w = jax.lax.map(
          lambda kk: (jax.random.normal(kk, shape[-2:], jnp.float32)
                      / math.sqrt(kind)).astype(dtype),
          jax.random.split(k, lead)).reshape(shape)
    else:
      w = jax.random.normal(k, shape, jnp.float32) / math.sqrt(kind)
    out[name] = w if kind in _F32 else w.astype(dtype)
  return out


def make_weights(seed: int, config: dict, dtype="float32"):
  """Stacked weights ``{leaf: array}`` from the seed, one jitted call on the
  default device. ``dtype`` is what the matrices are STORED in (``bfloat16``
  for serving: the model then IS the rounded numbers)."""
  import jax
  import jax.numpy as jnp
  z = sizes(config)
  key = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))
  return jax.jit(lambda k: _weights_impl(k, z, jnp.dtype(dtype)))(key)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def yarn_frequencies(z):
  """The ``rope / 2`` frequencies the rotated dims turn at (a list of Python
  floats): ``f_i = theta^(-2i/rope)``, blended towards ``f_i / factor`` by the
  ramp ``r_i = clip((i - low) / (high - low), 0, 1)`` with ``low =
  floor(d(beta_fast))``, ``high = ceil(d(beta_slow))``, ``d(n) = rope
  ln(original / (2 pi n)) / (2 ln theta)``."""
  dims, half = z["rope"], z["rope"] // 2

  def turns_at(n):
    return dims * math.log(z["yarn_original"] / (2 * math.pi * n)) \
        / (2 * math.log(z["theta"]))

  low = max(math.floor(turns_at(z["yarn_fast"])), 0)
  high = min(math.ceil(turns_at(z["yarn_slow"])), dims - 1)
  out = []
  for i in range(half):
    f = z["theta"] ** (-i / half)
    r = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
    out.append(f * (1.0 - r) + f / z["yarn_factor"] * r)
  return out


def softmax_scale(z, precision="f32"):
  """``(nope + rope)^-0.5 x m^2``; the ``no_mscale`` control leaves ``m^2``
  out."""
  base = (z["nope"] + z["rope"]) ** -0.5
  if precision == "no_mscale" or z["yarn_factor"] <= 1.0 \
      or not z["yarn_all_dim"]:
    return base
  return base * (0.1 * z["yarn_all_dim"] * math.log(z["yarn_factor"]) + 1.0) ** 2


def _lower(x, precision):
  """Matrix-multiplication inputs in the control's precision (``fp8``: the
  usual per-tensor-scaled e4m3 recipe; the controls of the mathematics round
  nothing)."""
  import jax.numpy as jnp
  x = x.astype(jnp.float32)
  if precision in ("f32", "no_group", "no_mscale"):
    return x
  if precision == "bf16":
    return x.astype(jnp.bfloat16).astype(jnp.float32)
  if precision == "fp8":
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
  raise ValueError("precision must be one of %r, got %r"
                   % (PRECISIONS, precision))


def _mm(spec, a, b, precision):
  import jax.numpy as jnp
  return jnp.einsum(spec, _lower(a, precision), _lower(b, precision))


def _rms_norm(x, scale, eps):
  import jax.numpy as jnp
  return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
  import jax
  return x * jax.nn.sigmoid(x)


def _rotate(x, positions, freqs):
  """Half-split rotary positions over ALL of the last axis of ``x [B, S, ...,
  D]`` at ``positions [S]``: the pair is ``(x[i], x[i + D/2])``, the angle
  ``position x freqs[i]``."""
  import jax.numpy as jnp
  half = x.shape[-1] // 2
  angles = positions.astype(jnp.float32)[:, None] \
      * jnp.asarray(freqs, jnp.float32)
  angles = angles.reshape((1, -1) + (1,) * (x.ndim - 3) + (half,))
  cos, sin = jnp.cos(angles), jnp.sin(angles)
  a, b = x[..., :half], x[..., half:]
  return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _attention(a, w, z, precision):
  """The attention branch over its normed input ``a [B, S, D]``: the latent
  and the rotated shared key of the whole sequence once; then a GROUP of heads
  at a time (its keys and values expanded from the latent) and inside it a
  block of queries at a time (scores against every position, causal mask),
  each group's output through its rows of ``W_o`` and summed: 128 heads x
  16384 x 16384 scores never exist at once, nor 128 heads' keys."""
  import jax
  import jax.numpy as jnp
  b, s, d = a.shape
  h, r, dn, dr, dv = z["heads"], z["kv_rank"], z["nope"], z["rope"], z["v_dim"]
  freqs, scale = yarn_frequencies(z), softmax_scale(z, precision)
  t = jnp.arange(s)
  cq = _rms_norm(_mm("bsd,dr->bsr", a, w["q_a"], precision), w["q_norm"],
                 z["eps"])
  kva = _mm("bsd,dc->bsc", a, w["kva"], precision)
  c = _rms_norm(kva[..., :r], w["kv_norm"], z["eps"])
  kr = _rotate(kva[..., r:], t, freqs)                    # [B, S, dr], shared
  hg = math.gcd(h, HEAD_GROUP)
  blk = math.gcd(s, QUERY_BLOCK)
  q_b = w["q_b"].reshape(-1, h // hg, hg, dn + dr)
  kvb = w["kvb"].reshape(r, h // hg, hg, dn + dv)
  wo = w["wo"].reshape(h // hg, hg * dv, d)

  def one_group(acc, g):
    kv = _mm("bsr,rhk->bshk", c, kvb[:, g], precision)    # [B, S, hg, dn+dv]
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(kr[:, :, None], (b, s, hg, dr))], -1)
    v = kv[..., dn:]

    def one_block(j):
      at = j * blk + jnp.arange(blk)
      cq_j = jax.lax.dynamic_slice_in_dim(cq, j * blk, blk, axis=1)
      q = _mm("bsr,rhk->bshk", cq_j, q_b[:, g], precision)
      q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], at, freqs)], -1)
      scores = _mm("bqhk,bthk->bhqt", q, k, precision) * scale
      scores = jnp.where(t[None, :] <= at[:, None], scores, -1e30)
      o = _mm("bhqt,bthk->bqhk", jax.nn.softmax(scores, axis=-1), v,
              precision)
      return _mm("bsw,wd->bsd", o.reshape(b, blk, hg * dv), wo[g], precision)

    out = jax.lax.map(one_block, jnp.arange(s // blk))    # [S/blk, B, blk, D]
    return acc + jnp.moveaxis(out, 0, 1).reshape(b, s, d), None

  return jax.lax.scan(one_group, jnp.zeros((b, s, d), jnp.float32),
                      jnp.arange(h // hg))[0]


def _swiglu(x, gate, up, down, precision):
  """``(silu(x gate) * (x up)) down`` over ``x [B, S, D]``, a block of the
  sequence at a time (the hidden layer of 16384 x 18432 is 1.2 GB, thrice)."""
  import jax
  import jax.numpy as jnp
  b, s, d = x.shape
  blk = math.gcd(s, TOKEN_BLOCK)

  def one(x_j):
    hidden = _silu(_mm("bsd,df->bsf", x_j, gate, precision)) \
        * _mm("bsd,df->bsf", x_j, up, precision)
    return _mm("bsf,fd->bsd", hidden, down, precision)

  out = jax.lax.map(one, jnp.moveaxis(x.reshape(b, s // blk, blk, d), 1, 0))
  return jnp.moveaxis(out, 0, 1).reshape(b, s, -1)


def route(x, w, z, precision="f32"):
  """``(experts [B, S, k], weights [B, S, k])``: float32 whatever the
  control's precision (a router in fp8 is another model, not a rounding);
  ``"no_group"`` leaves the group limit out (the top k over every expert)."""
  import jax
  import jax.numpy as jnp
  s = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x,
                                w["router"].astype(jnp.float32)))
  choice = s + w["router_bias"]
  if precision != "no_group":
    g, per = z["groups"], z["routed"] // z["groups"]
    grouped = choice.reshape(choice.shape[:2] + (g, per))
    score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)        # [B, S, g]
    kept = jax.lax.top_k(score, z["groups_kept"])[1]
    keep = jnp.sum(jax.nn.one_hot(kept, g, dtype=jnp.float32), axis=2) > 0
    choice = jnp.where(keep[..., None], grouped,
                       -jnp.inf).reshape(choice.shape)
  _, experts = jax.lax.top_k(choice, z["top_k"])
  picked = jnp.take_along_axis(s, experts, axis=-1)
  return experts, picked / (jnp.sum(picked, -1, keepdims=True) + ROUTE_EPS) \
      * z["scale"]


def _experts(x, w, z, precision):
  """Every token through each HELD expert in turn, weighted by its routing
  (0 where the token did not choose it); experts held elsewhere add nothing.
  One expert's matrices are upcast at a time. Plus the shared expert."""
  import jax
  import jax.numpy as jnp
  experts, weights = route(x, w, z, precision)
  local = experts - z["first"]

  def matrix(name, e):
    # from the layer's stack [held, ...], or (``_layer_weights``) straight
    # from the model's [layers, held, ...]: a layer's stack is 1.4 GB
    stack = w[name]
    return stack[e] if stack.ndim == 3 else stack[w["exp_at"], e]

  def one(e, acc):
    mine = jnp.sum(jnp.where(local == e, weights, 0.0), axis=-1)   # [B, S]
    out = _swiglu(x, matrix("exp_gate", e), matrix("exp_up", e),
                  matrix("exp_down", e), precision)
    return acc + out * mine[..., None]

  routed = jax.lax.fori_loop(0, z["held"], one, jnp.zeros_like(x))
  if not z["shared"]:
    return routed
  return routed + _swiglu(x, w["shared_gate"], w["shared_up"],
                          w["shared_down"], precision)


_ATTN = ("ln1", "ln2", "q_a", "q_norm", "q_b", "kva", "kv_norm", "kvb", "wo")


def _layer_weights(weights, z, i):
  """Layer ``i``'s (0-based) leaves out of the stacks, as stored; the routed
  experts' stacks stay whole, with the layer's index in them under
  ``exp_at`` (``_experts`` takes one expert's matrices at a time)."""
  w = {n: weights[n][i] for n in _ATTN}
  if i < z["dense_layers"]:
    w.update({n: weights[n][i] for n in ("mlp_gate", "mlp_up", "mlp_down")})
    return w
  j = i - z["dense_layers"]
  w.update({n: weights[n][j] for n in (
      "router", "router_bias", "shared_gate", "shared_up", "shared_down")})
  w.update({n: weights[n] for n in ("exp_gate", "exp_up", "exp_down")},
           exp_at=j)
  return w


def _layer(x, w, z, i, precision, routing=None):
  """Layer ``i`` over ``x [B, S, D]``; ``w`` its own leaves as stored."""
  x = x + _attention(_rms_norm(x, w["ln1"], z["eps"]), w, z, precision)
  y = _rms_norm(x, w["ln2"], z["eps"])
  if i < z["dense_layers"]:
    return x + _swiglu(y, w["mlp_gate"], w["mlp_up"], w["mlp_down"],
                       precision)
  if routing is not None:
    routing.append(route(y, w, z, precision)[0])
  return x + _experts(y, w, z, precision)


def reference_layer(weights, x, config: dict, i: int,
                    precision: str = "f32", routing: list = None):
  """Layer ``i`` (0-based, of the layers kept) over ``x [B, S, D]``.
  ``routing`` (a list) collects an expert layer's choices ``[B, S, k]``."""
  import jax
  z = sizes(config)
  with jax.default_matmul_precision("highest"):
    return _layer(x, _layer_weights(weights, z, i), z, i, precision, routing)


def reference_logits(weights, tokens, config: dict, precision: str = "f32",
                     routing: list = None):
  """Logits ``[B, S, V]`` (float32) of the plain model over ``tokens``."""
  import jax
  import jax.numpy as jnp
  z = sizes(config)
  with jax.default_matmul_precision("highest"):
    x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    for i in range(z["layers"]):
      x = _layer(x, _layer_weights(weights, z, i), z, i, precision, routing)
    x = _rms_norm(x, weights["ln_f"].astype(jnp.float32), z["eps"])
    return _mm("bsd,dv->bsv", x, weights["head"], precision)


# ---------------------------------------------------------------------------
# the program half
# ---------------------------------------------------------------------------


def program_config(config: dict, max_seq_len: int, **overrides):
  """The program's ``TransformerConfig`` at this configuration's sizes."""
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import transformer as tfm
  z = sizes(config)
  kw = dict(
      vocab_size=z["vocab"], num_layers=z["layers"], num_heads=z["heads"],
      d_model=z["d_model"], d_ff=z["d_ff"], max_seq_len=int(max_seq_len),
      remat=False,
      dtype=jnp.dtype(config.get("compute_dtype", "bfloat16")),
      layer_types=("mla",) * z["layers"],
      ffn_types=tuple("mlp" if i < z["dense_layers"] else "experts"
                      for i in range(z["layers"])),
      norm="rms", norm_eps=z["eps"], mlp_act="swiglu", tie_embeddings=False,
      mla_kv_rank=z["kv_rank"], mla_nope_dim=z["nope"],
      mla_rope_dim=z["rope"], mla_v_dim=z["v_dim"], mla_q_rank=z["q_rank"],
      mla_rope=True, rope_theta=z["theta"], rope_yarn_factor=z["yarn_factor"],
      rope_yarn_original=z["yarn_original"],
      rope_yarn_beta_fast=z["yarn_fast"], rope_yarn_beta_slow=z["yarn_slow"],
      rope_yarn_mscale_all_dim=z["yarn_all_dim"],
      experts_total=z["routed"], experts_held=z["held"],
      experts_first=z["first"], experts_top_k=z["top_k"],
      experts_d_ff=z["expert_ff"], experts_shared=z["shared"],
      experts_scale=z["scale"], experts_groups=z["groups"],
      experts_groups_kept=z["groups_kept"],
      act_f32=bool(config.get("float32_activations", False)))
  kw.update(overrides)
  return tfm.TransformerConfig(**kw)


def _to_program_tree(w, z):
  d, h = z["d_model"], z["heads"]
  dn, dr, dv = z["nope"], z["rope"], z["v_dim"]
  tree = {"embed": {"embedding": w["embed"]}, "head": {"kernel": w["head"]},
          "ln_f": {"scale": w["ln_f"]}}
  for i in range(z["layers"]):
    lw = _layer_weights(w, z, i)
    layer = {n: {"scale": lw[n]} for n in ("ln1", "ln2")}
    layer["mla"] = {
        "q_a": {"kernel": lw["q_a"]}, "q_norm": {"scale": lw["q_norm"]},
        "q_b": {"kernel": lw["q_b"].reshape(-1, h, dn + dr)},
        "kva": {"kernel": lw["kva"]}, "kv_norm": {"scale": lw["kv_norm"]},
        "kvb": lw["kvb"].reshape(-1, h, dn + dv),
        "out": {"kernel": lw["wo"].reshape(h, dv, d)}}
    if i < z["dense_layers"]:
      layer["mlp"] = {n: {"kernel": lw["mlp_" + n]}
                      for n in ("gate", "up", "down")}
    else:
      j = lw["exp_at"]
      moe = {"router": lw["router"], "router_bias": lw["router_bias"],
             "gate": w["exp_gate"][j], "up": w["exp_up"][j],
             "down": w["exp_down"][j]}
      if z["shared"]:
        moe["shared"] = {n: {"kernel": lw["shared_" + n]}
                         for n in ("gate", "up", "down")}
      layer["moe"] = moe
    tree["layer_%d" % i] = layer
  return tree


def program_params(seed: int, config: dict, dtype="float32"):
  """The same weights as ``make_weights(seed, config, dtype)``, in the
  program's tree layout; one jitted call on the device."""
  import jax
  import jax.numpy as jnp
  z = sizes(config)
  key = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))
  return jax.jit(lambda k: _to_program_tree(
      _weights_impl(k, z, jnp.dtype(dtype)), z))(key)
