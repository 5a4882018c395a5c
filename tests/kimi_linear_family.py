"""The ``kimi_linear`` family: how a Kimi-Linear configuration file becomes
(a) the benchmark's own plain reference and (b) the program's
``TransformerConfig`` and parameter tree.

Two halves, kept apart on purpose (as ``families/gpt2.py``):

* **the reference half** (``make_weights``, ``reference_logits``,
  ``reference_layer``) imports nothing of the program. It is the model of
  the Kimi Linear report (arXiv:2510.26692) in straightforward
  ``jax.numpy``: pre-norm residual blocks ``x += Mix(RMSNorm(x)); x +=
  FFN(RMSNorm(x))``, final RMSNorm, untied head; the mixer is KDA (gated
  delta rule, computed as the PER-TOKEN recurrence: no chunks, no cache) or
  MLA without rotary positions (keys and values expanded per head, plain
  causal softmax); the feed-forward is a dense SwiGLU MLP (the first
  ``first_k_dense_replace`` layers) or sparse experts with a sigmoid router.
  float32 with ``jax.default_matmul_precision("highest")``. ``precision``
  swaps the matrix multiplications' inputs to a lower precision: the CONTROL
  of the ``correct`` decision, never a speed-up.
* **the program half** (``program_config``, ``program_params``) is the only
  place that touches ``tensorflowonspark_tpu``.

The configuration is ONE CHIP'S SHARE of an expert-parallel deployment:
``num_experts`` counts the experts HELD here (``experts_first`` the first of
them), ``num_experts_published`` is the router's width. The reference gets
the same share: an assignment to an expert held elsewhere adds nothing, in
both. ``tests/kimi_linear_family.py`` is a byte-for-byte copy of this file
(``benchmarks/tests/test_kimi_linear.py`` keeps them equal), so that tier-1
tests need nothing of ``benchmarks/``.
"""

import math

EMBED_STD = 0.02
L2_EPS = 1e-6
PRECISIONS = ("f32", "bf16", "fp8")


def sizes(config: dict) -> dict:
  """The model's sizes from a ``config.json``-style dict (Hugging Face
  ``kimi_linear`` key names, plus the cut's and the assumed keys)."""
  lin = config["linear_attn_config"]
  layers = int(config["num_hidden_layers"])
  kda = sorted(int(i) for i in lin["kda_layers"])        # 1-based, as published
  mla = sorted(int(i) for i in lin["full_attn_layers"])
  if sorted(kda + mla) != list(range(1, layers + 1)):
    raise ValueError("kda_layers and full_attn_layers must partition "
                     "1..%d" % layers)
  dense = int(config["first_k_dense_replace"])
  z = dict(
      vocab=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
      layers=layers, kda_layers=tuple(kda), mla_layers=tuple(mla),
      dense_layers=dense, eps=float(config["rms_norm_eps"]),
      kda_heads=int(lin["num_heads"]), kda_dim=int(lin["head_dim"]),
      taps=int(lin["short_conv_kernel_size"]),
      kda_rank=int(config["kda_low_rank_dim"]),
      heads=int(config["num_attention_heads"]),
      kv_rank=int(config["kv_lora_rank"]),
      nope=int(config["qk_nope_head_dim"]),
      rope=int(config["qk_rope_head_dim"]), v_dim=int(config["v_head_dim"]),
      d_ff=int(config["intermediate_size"]),
      expert_ff=int(config["moe_intermediate_size"]),
      held=int(config["num_experts"]),
      first=int(config.get("experts_first", 0)),
      routed=int(config["num_experts_published"]),
      top_k=int(config["num_experts_per_token"]),
      shared=int(config["num_shared_experts"]),
      scale=float(config["routed_scaling_factor"]))
  if config.get("q_lora_rank") is not None:
    raise ValueError("a low-rank query map is not this family's")
  if not config.get("mla_use_nope", False):
    raise ValueError("this family's MLA does not rotate its shared key")
  if config.get("num_expert_group", 1) != 1 or config.get("topk_group", 1) != 1:
    raise ValueError("grouped routing limits are not this family's")
  return z


def param_count(config: dict) -> int:
  z = sizes(config)
  return sum(math.prod(shape) for _, shape, _ in _leaves(z))


# ---------------------------------------------------------------------------
# weights: one jitted call from the seed, stacked over the layers of a kind
# ---------------------------------------------------------------------------


def _leaves(z):
  """``(name, shape, kind)``: ``kind`` an int = fan-in of a normal matrix,
  or the name of a special draw."""
  d, nk, nm = z["d_model"], len(z["kda_layers"]), len(z["mla_layers"])
  nd, ne = z["dense_layers"], z["layers"] - z["dense_layers"]
  w, h, r = z["kda_heads"] * z["kda_dim"], z["kda_heads"], z["kda_rank"]
  hq = z["heads"]
  f, fs = z["expert_ff"], z["shared"] * z["expert_ff"]
  return (
      ("embed", (z["vocab"], d), "embed"), ("head", (d, z["vocab"]), d),
      ("ln_f", (d,), "ones"),
      ("ln1", (z["layers"], d), "ones"), ("ln2", (z["layers"], d), "ones"),
      # KDA
      ("kda_q", (nk, d, w), d), ("kda_k", (nk, d, w), d),
      ("kda_v", (nk, d, w), d), ("kda_conv", (nk, z["taps"], 3 * w), "conv"),
      ("kda_f1", (nk, d, r), d), ("kda_f2", (nk, r, w), r),
      ("kda_dt_bias", (nk, w), "dt_bias"), ("kda_A_log", (nk, h), "A_log"),
      ("kda_b", (nk, d, h), d),
      ("kda_g1", (nk, d, r), d), ("kda_g2", (nk, r, w), r),
      ("kda_o_norm", (nk, z["kda_dim"]), "ones"), ("kda_out", (nk, w, d), w),
      # MLA
      ("mla_q", (nm, d, hq, z["nope"] + z["rope"]), d),
      ("mla_kva", (nm, d, z["kv_rank"] + z["rope"]), d),
      ("mla_kv_norm", (nm, z["kv_rank"]), "ones"),
      ("mla_kvb", (nm, z["kv_rank"], hq, z["nope"] + z["v_dim"]),
       z["kv_rank"]),
      ("mla_out", (nm, hq, z["v_dim"], d), hq * z["v_dim"]),
      # dense MLP layers, then expert layers
      ("mlp_gate", (nd, d, z["d_ff"]), d), ("mlp_up", (nd, d, z["d_ff"]), d),
      ("mlp_down", (nd, z["d_ff"], d), z["d_ff"]),
      ("router", (ne, d, z["routed"]), "router"),
      ("router_bias", (ne, z["routed"]), "router_bias"),
      ("exp_gate", (ne, z["held"], d, f), d),
      ("exp_up", (ne, z["held"], d, f), d),
      ("exp_down", (ne, z["held"], f, d), f),
      ("shared_gate", (ne, d, fs), d), ("shared_up", (ne, d, fs), d),
      ("shared_down", (ne, fs, d), fs),
  )


#: leaves kept in float32 whatever the matrices are stored in: norm scales,
#: the decay's parameters and the router (its scores decide a top-k)
_F32 = ("ones", "dt_bias", "A_log", "router", "router_bias")


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
    elif kind == "conv":
      # each tap ~ N(0, 1/taps): the convolved projection keeps its scale
      w = jax.random.normal(k, shape, jnp.float32) / math.sqrt(shape[1])
    elif kind == "A_log":
      # exp(A_log) log-uniform over [1/4, 4], with dt_bias below: a_t =
      # exp(-exp(A_log) softplus(. + dt_bias)) spreads over (0, 1)
      w = jax.random.uniform(k, shape, jnp.float32, math.log(0.25),
                             math.log(4.0))
    elif kind == "dt_bias":
      w = jax.random.uniform(k, shape, jnp.float32, -4.0, 1.0)
    elif kind == "router":
      w = jax.random.normal(k, shape, jnp.float32) / math.sqrt(shape[1])
    elif kind == "router_bias":
      w = jax.random.normal(k, shape, jnp.float32) * 0.02
    elif len(shape) > 2:
      # a stack over layers, drawn and rounded a layer at a time: drawn
      # whole, the float32 normals of the largest stack (26 x 16 experts)
      # are 3.9 GB of scratch that the process then counts at its peak
      w = jax.lax.map(
          lambda kk: (jax.random.normal(kk, shape[1:], jnp.float32)
                      / math.sqrt(kind)).astype(dtype),
          jax.random.split(k, shape[0]))
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


def _lower(x, precision):
  """Matrix-multiplication inputs in the control's precision (``fp8``: the
  usual per-tensor-scaled e4m3 recipe)."""
  import jax.numpy as jnp
  x = x.astype(jnp.float32)
  if precision == "f32":
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


def _l2norm(x):
  import jax.numpy as jnp
  return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _kda(x, w, z, precision):
  """``x [B, S, D]`` -> ``[B, S, D]``: the per-token recurrence."""
  import jax
  import jax.numpy as jnp
  b, s, _ = x.shape
  h, dk, taps = z["kda_heads"], z["kda_dim"], z["taps"]
  qkv = jnp.concatenate(
      [_mm("bsd,dw->bsw", x, w["kda_" + n], precision) for n in "qkv"], -1)
  padded = jnp.pad(qkv, [(0, 0), (taps - 1, 0), (0, 0)])
  conv = w["kda_conv"].astype(jnp.float32)
  mixed = sum(conv[j] * padded[:, j:j + s] for j in range(taps))
  q, k, v = (t.reshape(b, s, h, dk)
             for t in jnp.split(_silu(mixed), 3, axis=-1))
  q, k = _l2norm(q) * dk ** -0.5, _l2norm(k)
  f = _mm("bsr,rw->bsw", _mm("bsd,dr->bsr", x, w["kda_f1"], precision),
          w["kda_f2"], precision)
  a = jnp.exp(-jnp.exp(w["kda_A_log"])[:, None] * jax.nn.softplus(
      (f + w["kda_dt_bias"]).reshape(b, s, h, dk)))          # in (0, 1)
  beta = jax.nn.sigmoid(_mm("bsd,dh->bsh", x, w["kda_b"], precision))
  gate = jax.nn.sigmoid(_mm(
      "bsr,rw->bsw", _mm("bsd,dr->bsr", x, w["kda_g1"], precision),
      w["kda_g2"], precision)).reshape(b, s, h, dk)

  def step(state, t):                       # state [B, H, dk, dv]
    q_t, k_t, v_t, a_t, b_t = t
    decayed = a_t[..., None] * state
    u = v_t - _mm("bhkv,bhk->bhv", decayed, k_t, precision)
    state = decayed + b_t[..., None, None] * k_t[..., None] * u[:, :, None]
    return state, _mm("bhkv,bhk->bhv", state, q_t, precision)

  seq = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, a, beta))
  _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, dk), jnp.float32), seq)
  o = _rms_norm(jnp.moveaxis(o, 0, 1), w["kda_o_norm"], z["eps"]) * gate
  return _mm("bsw,wd->bsd", o.reshape(b, s, h * dk), w["kda_out"], precision)


def _mla(x, w, z, precision):
  import jax
  import jax.numpy as jnp
  s, r, dn = x.shape[1], z["kv_rank"], z["nope"]
  q = _mm("bsd,dhk->bshk", x, w["mla_q"], precision)
  kva = _mm("bsd,dc->bsc", x, w["mla_kva"], precision)
  c = _rms_norm(kva[..., :r], w["mla_kv_norm"], z["eps"])
  kv = _mm("bsr,rhk->bshk", c, w["mla_kvb"], precision)
  shared = jnp.broadcast_to(kva[:, :, None, r:],
                            kv.shape[:3] + (z["rope"],))     # NOT rotated
  k = jnp.concatenate([kv[..., :dn], shared], axis=-1)
  scores = _mm("bqhk,bthk->bhqt", q, k, precision) \
      / math.sqrt(dn + z["rope"])
  causal = jnp.tril(jnp.ones((s, s), bool))
  probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -1e30), -1)
  att = _mm("bhqt,bthk->bqhk", probs, kv[..., dn:], precision)
  return _mm("bqhk,hkd->bqd", att, w["mla_out"], precision)


def _swiglu(x, gate, up, down, precision):
  hidden = _silu(_mm("bsd,df->bsf", x, gate, precision)) \
      * _mm("bsd,df->bsf", x, up, precision)
  return _mm("bsf,fd->bsd", hidden, down, precision)


def route(x, w, z):
  """``(experts [B, S, k], weights [B, S, k])``: float32 whatever the
  control's precision (a router in fp8 is another model, not a rounding)."""
  import jax
  import jax.numpy as jnp
  s = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x, w["router"]))
  _, experts = jax.lax.top_k(s + w["router_bias"], z["top_k"])
  picked = jnp.take_along_axis(s, experts, axis=-1)
  return experts, picked / jnp.sum(picked, -1, keepdims=True) * z["scale"]


def _experts(x, w, z, precision):
  """Every token through every HELD expert, weighted by its routing (0 where
  the token did not choose it); experts held elsewhere add nothing."""
  import jax
  import jax.numpy as jnp
  experts, weights = route(x, w, z)
  local = experts - z["first"]
  combine = jnp.sum(
      jax.nn.one_hot(local, z["held"], dtype=jnp.float32)    # OOB rows: zeros
      * weights[..., None], axis=2)                          # [B, S, held]
  hidden = _silu(_mm("bsd,edf->bsef", x, w["exp_gate"], precision)) \
      * _mm("bsd,edf->bsef", x, w["exp_up"], precision)
  out = _mm("bsef,efd->bsed", hidden, w["exp_down"], precision)
  routed = jnp.einsum("bsed,bse->bsd", out, combine)
  if not z["shared"]:
    return routed
  return routed + _swiglu(x, w["shared_gate"], w["shared_up"],
                          w["shared_down"], precision)


def _layer_weights(weights, z, i):
  """Layer ``i``'s (0-based) leaves out of the stacks, as float32."""
  import jax.numpy as jnp
  one = i + 1
  picks = {"ln1": i, "ln2": i}
  kind = "kda" if one in z["kda_layers"] else "mla"
  group = z[kind + "_layers"]
  picks.update({n: group.index(one) for n in weights
                if n.startswith(kind + "_")})
  if i < z["dense_layers"]:
    picks.update({n: i for n in weights if n.startswith("mlp_")})
  else:
    picks.update({n: i - z["dense_layers"] for n in weights
                  if n.startswith(("router", "exp_", "shared_"))})
  return kind, {n: weights[n][j].astype(jnp.float32)
                for n, j in picks.items()}


def reference_layer(weights, x, config: dict, i: int,
                    precision: str = "f32", routing: list = None):
  """Layer ``i`` (0-based) of the model over ``x [B, S, D]``. ``routing``
  (a list) collects an expert layer's choices ``[B, S, k]``."""
  z = sizes(config)
  kind, w = _layer_weights(weights, z, i)
  y = _rms_norm(x, w["ln1"], z["eps"])
  x = x + (_kda if kind == "kda" else _mla)(y, w, z, precision)
  y = _rms_norm(x, w["ln2"], z["eps"])
  if i < z["dense_layers"]:
    return x + _swiglu(y, w["mlp_gate"], w["mlp_up"], w["mlp_down"],
                       precision)
  if routing is not None:
    routing.append(route(y, w, z)[0])
  return x + _experts(y, w, z, precision)


def reference_logits(weights, tokens, config: dict, precision: str = "f32",
                     routing: list = None):
  """Logits ``[B, S, V]`` (float32) of the plain model over ``tokens``."""
  import jax
  import jax.numpy as jnp
  z = sizes(config)
  with jax.default_matmul_precision("highest"):
    x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    for i in range(z["layers"]):
      x = reference_layer(weights, x, config, i, precision, routing)
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
      layer_types=tuple("kda" if i + 1 in z["kda_layers"] else "mla"
                        for i in range(z["layers"])),
      ffn_types=tuple("mlp" if i < z["dense_layers"] else "experts"
                      for i in range(z["layers"])),
      norm="rms", norm_eps=z["eps"], mlp_act="swiglu", tie_embeddings=False,
      kda_heads=z["kda_heads"], kda_head_dim=z["kda_dim"],
      kda_conv=z["taps"], kda_rank=z["kda_rank"],
      mla_kv_rank=z["kv_rank"], mla_nope_dim=z["nope"],
      mla_rope_dim=z["rope"], mla_v_dim=z["v_dim"],
      experts_total=z["routed"], experts_held=z["held"],
      experts_first=z["first"], experts_top_k=z["top_k"],
      experts_d_ff=z["expert_ff"], experts_shared=z["shared"],
      experts_scale=z["scale"],
      act_f32=bool(config.get("float32_activations", False)))
  kw.update(overrides)
  return tfm.TransformerConfig(**kw)


_KDA = {"q": "kda_q", "k": "kda_k", "v": "kda_v", "f1": "kda_f1",
        "f2": "kda_f2", "b": "kda_b", "g1": "kda_g1", "g2": "kda_g2",
        "out": "kda_out"}


def _to_program_tree(w, z):
  tree = {"embed": {"embedding": w["embed"]}, "head": {"kernel": w["head"]},
          "ln_f": {"scale": w["ln_f"]}}
  for i in range(z["layers"]):
    layer = {"ln1": {"scale": w["ln1"][i]}, "ln2": {"scale": w["ln2"][i]}}
    one = i + 1
    if one in z["kda_layers"]:
      j = z["kda_layers"].index(one)
      kda = {n: {"kernel": w[src][j]} for n, src in _KDA.items()}
      kda.update(conv=w["kda_conv"][j], A_log=w["kda_A_log"][j],
                 dt_bias=w["kda_dt_bias"][j], o_norm=w["kda_o_norm"][j])
      layer["kda"] = kda
    else:
      j = z["mla_layers"].index(one)
      layer["mla"] = {"q": {"kernel": w["mla_q"][j]},
                      "kva": {"kernel": w["mla_kva"][j]},
                      "kv_norm": {"scale": w["mla_kv_norm"][j]},
                      "kvb": w["mla_kvb"][j],
                      "out": {"kernel": w["mla_out"][j]}}
    if i < z["dense_layers"]:
      layer["mlp"] = {n: {"kernel": w["mlp_" + n][i]}
                      for n in ("gate", "up", "down")}
    else:
      j = i - z["dense_layers"]
      moe = {"router": w["router"][j], "router_bias": w["router_bias"][j],
             "gate": w["exp_gate"][j], "up": w["exp_up"][j],
             "down": w["exp_down"][j]}
      if z["shared"]:
        moe["shared"] = {n: {"kernel": w["shared_" + n][j]}
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
