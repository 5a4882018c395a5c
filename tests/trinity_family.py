"""The ``trinity`` family: how an ``afmoe`` configuration file (arcee-ai's
Trinity) becomes (a) the benchmark's own plain reference and (b) the
program's ``TransformerConfig`` and parameter tree.

Two halves, kept apart on purpose (as ``families/gpt2.py``):

* **the reference half** (``make_weights``, ``reference_logits``,
  ``reference_layer``, ``route``) imports nothing of the program. It is the
  model in straightforward ``jax.numpy``, a FULL forward pass with no cache
  and no ring: ``x = sqrt(hidden) E[tokens]``; a layer is ``x += RMSNorm(
  (Attn(RMSNorm(x)) * sigmoid(gate)) Wo); x += RMSNorm(FFN(RMSNorm(x)))`` (a
  norm before AND after each branch); attention is grouped-query (query head
  ``h`` reads KV head ``h // g``) with an RMSNorm over each head's
  ``head_dim`` of the queries and of the keys (one scale each, shared by the
  heads); a SLIDING layer rotates queries and keys (half-split rotary, the
  token's position) and attends positions ``i - window < j <= i``, written
  as a MASK over the whole sequence; a FULL layer does NOT rotate and
  attends every ``j <= i``; the gate ``sigmoid(a W_gate)`` multiplies the
  heads' concatenated output before ``Wo``; the feed-forward is a dense
  gated-SiLU MLP (the leading ``num_dense_layers``) or sparse experts:
  ``s = sigmoid(c W_r)`` in float32, the ``k`` largest of ``s + b`` chosen
  (the bias for the selection only), weights ``s_e / (sum of the chosen s +
  1e-20) x route_scale``, plus one shared expert; final RMSNorm, untied head.
  float32 with ``jax.default_matmul_precision("highest")``; attention runs
  a block of queries at a time, a feed-forward a block of tokens at a time,
  and the held experts are upcast and applied ONE AT A TIME, so that 16384
  positions fit beside the bf16 weights. ``precision``
  swaps the matrix multiplications' inputs to a lower precision: the CONTROL
  of the ``correct`` decision, never a speed-up.
* **the program half** (``program_config``, ``program_params``) is the only
  place that touches ``tensorflowonspark_tpu``.

The configuration is ONE CHIP'S SHARE of an expert-parallel deployment and
a CUT IN DEPTH: ``num_experts`` counts the experts HELD here
(``experts_first`` the first of them), ``num_experts_published`` is the
router's width; ``num_hidden_layers`` layers are kept, from published layer
``first_layer_published`` (1-based) on, their kinds read off the whole
published ``layer_types``; ``num_dense_layers`` of them are dense. The
reference gets the same share: an assignment to an expert held elsewhere
adds nothing, in both. ``tests/trinity_family.py`` is a byte-for-byte copy
of this file (``benchmarks/tests/test_trinity.py`` keeps them equal), so
that tier-1 tests need nothing of ``benchmarks/``.
"""

import math

EMBED_STD = 0.02
ROUTE_EPS = 1e-20
#: queries a block of the reference's attention (scores of 48 heads x 128 x
#: 16384 keys are 0.4 GB in float32)
QUERY_BLOCK = 128
#: tokens a block of the reference's feed-forward
TOKEN_BLOCK = 2048
PRECISIONS = ("f32", "bf16", "fp8")


def sizes(config: dict) -> dict:
  """The model's sizes from a ``config.json``-style dict (Hugging Face
  ``afmoe`` key names, plus the cut's keys)."""
  layers = int(config["num_hidden_layers"])
  first = int(config.get("first_layer_published", 1)) - 1
  kinds = tuple(config["layer_types"][first:first + layers])
  if len(kinds) != layers or any(
      k not in ("sliding_attention", "full_attention") for k in kinds):
    raise ValueError("layer_types must name sliding_attention or "
                     "full_attention for layers %d..%d"
                     % (first + 1, first + layers))
  z = dict(
      vocab=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
      layers=layers, sliding=tuple(k == "sliding_attention" for k in kinds),
      window=int(config["sliding_window"]),
      heads=int(config["num_attention_heads"]),
      kv_heads=int(config["num_key_value_heads"]),
      head_dim=int(config["head_dim"]), d_ff=int(config["intermediate_size"]),
      expert_ff=int(config["moe_intermediate_size"]),
      dense_layers=int(config["num_dense_layers"]),
      held=int(config["num_experts"]),
      first=int(config.get("experts_first", 0)),
      routed=int(config["num_experts_published"]),
      top_k=int(config["num_experts_per_tok"]),
      shared=int(config["num_shared_experts"]),
      scale=float(config["route_scale"]),
      eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]),
      embed_scale=math.sqrt(int(config["hidden_size"]))
      if config.get("mup_enabled", False) else 1.0)
  if config.get("score_func", "sigmoid") != "sigmoid" \
      or not config.get("route_norm", True):
    raise ValueError("this family's router is a sigmoid with renormalised "
                     "weights")
  if config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
    raise ValueError("grouped routing limits are not this family's")
  if config.get("rope_scaling") is not None:
    raise ValueError("scaled rotary positions are not this family's")
  if config.get("hidden_act", "silu") != "silu" \
      or config.get("tie_word_embeddings", False):
    raise ValueError("this family's MLP is gated SiLU and its head untied")
  if z["heads"] % z["kv_heads"]:
    raise ValueError("KV heads must divide the query heads")
  return z


def _leaves(z):
  """``(name, shape, kind)``: ``kind`` an int = fan-in of a normal matrix,
  or the name of a special draw."""
  d, n, dh = z["d_model"], z["layers"], z["head_dim"]
  nd, ne = z["dense_layers"], z["layers"] - z["dense_layers"]
  wq, wkv = z["heads"] * dh, z["kv_heads"] * dh
  f, fs = z["expert_ff"], z["shared"] * z["expert_ff"]
  return (
      ("embed", (z["vocab"], d), "embed"), ("head", (d, z["vocab"]), d),
      ("ln_f", (d,), "ones"),
      ("ln1", (n, d), "ones"), ("ln1_out", (n, d), "ones"),
      ("ln2", (n, d), "ones"), ("ln2_out", (n, d), "ones"),
      ("q_norm", (n, dh), "ones"), ("k_norm", (n, dh), "ones"),
      ("wq", (n, d, wq), d), ("wk", (n, d, wkv), d), ("wv", (n, d, wkv), d),
      ("wg", (n, d, wq), d), ("wo", (n, wq, d), wq),
      # the leading dense layers' MLP, then the expert layers
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


def param_count(config: dict) -> int:
  return sum(math.prod(shape) for _, shape, _ in _leaves(sizes(config)))


#: leaves kept in float32 whatever the matrices are stored in: norm scales
#: and the router (its scores decide a top-k)
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
      # time: drawn whole, the float32 normals of an expert stack (4 x 32 x
      # 3072 x 3072) are 4.8 GB of scratch that the process then counts at
      # its peak
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


def _rotate(x, positions, theta):
  """Half-split rotary positions over ``x [B, S, H, D]`` at ``positions
  [S]``: the pair is ``(x[i], x[i + D/2])``, the angle ``position x
  theta^(-2i/D)``."""
  import jax.numpy as jnp
  half = x.shape[-1] // 2
  freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
  angles = positions.astype(jnp.float32)[:, None] * freqs
  cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
  a, b = x[..., :half], x[..., half:]
  return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _attention(a, w, z, i, precision):
  """The attention branch of layer ``i`` over its normed input ``a [B, S,
  D]``, before the output norm: keys and values of the whole sequence, then a
  block of queries at a time (its projections, its scores against every
  position with the window a MASK, its gate, its output projection), so that
  48 heads x 16384 x 16384 scores never exist at once."""
  import jax
  import jax.numpy as jnp
  b, s, _ = a.shape
  h, hk, dh = z["heads"], z["kv_heads"], z["head_dim"]
  sliding = z["sliding"][i]
  window = z["window"] if sliding else 0
  k = _rms_norm(_mm("bsd,dw->bsw", a, w["wk"], precision).reshape(
      b, s, hk, dh), w["k_norm"], z["eps"])
  v = _mm("bsd,dw->bsw", a, w["wv"], precision).reshape(b, s, hk, dh)
  if sliding:                        # a full layer has no positional term
    k = _rotate(k, jnp.arange(s), z["theta"])
  blk = math.gcd(s, QUERY_BLOCK)
  t = jnp.arange(s)

  def one(j):
    at = j * blk + jnp.arange(blk)
    a_j = jax.lax.dynamic_slice_in_dim(a, j * blk, blk, axis=1)
    q = _rms_norm(_mm("bsd,dw->bsw", a_j, w["wq"], precision).reshape(
        b, blk, h, dh), w["q_norm"], z["eps"])
    if sliding:
      q = _rotate(q, at, z["theta"])
    # query head h reads KV head h // g
    scores = _mm("bqkgd,btkd->bkgqt", q.reshape(b, blk, hk, h // hk, dh), k,
                 precision) / math.sqrt(dh)
    keep = t[None, :] <= at[:, None]
    if window:
      keep = jnp.logical_and(keep, t[None, :] > at[:, None] - window)
    probs = jax.nn.softmax(jnp.where(keep, scores, -1e30), axis=-1)
    o = _mm("bkgqt,btkd->bqkgd", probs, v, precision).reshape(b, blk, h * dh)
    gate = jax.nn.sigmoid(_mm("bsd,dw->bsw", a_j, w["wg"], precision))
    return _mm("bsw,wd->bsd", o * gate, w["wo"], precision)

  out = jax.lax.map(one, jnp.arange(s // blk))          # [S/blk, B, blk, D]
  return jnp.moveaxis(out, 0, 1).reshape(b, s, -1)


def _swiglu(x, gate, up, down, precision):
  """``(silu(x gate) * (x up)) down`` over ``x [B, S, D]``, a block of the
  sequence at a time (the hidden layer of 16384 x 12288 is 0.8 GB, thrice)."""
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


def route(x, w, z):
  """``(experts [B, S, k], weights [B, S, k])``: float32 whatever the
  control's precision (a router in fp8 is another model, not a rounding)."""
  import jax
  import jax.numpy as jnp
  s = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x,
                                w["router"].astype(jnp.float32)))
  _, experts = jax.lax.top_k(s + w["router_bias"], z["top_k"])
  picked = jnp.take_along_axis(s, experts, axis=-1)
  return experts, picked / (jnp.sum(picked, -1, keepdims=True)
                            + ROUTE_EPS) * z["scale"]


def _experts(x, w, z, precision):
  """Every token through each HELD expert in turn, weighted by its routing
  (0 where the token did not choose it); experts held elsewhere add nothing.
  One expert's matrices are upcast at a time."""
  import jax
  import jax.numpy as jnp
  experts, weights = route(x, w, z)
  local = experts - z["first"]

  def matrix(name, e):
    # from the layer's stack [held, ...], or (``_layer_weights``) straight
    # from the model's [layers, held, ...]: a layer's stack is 1.8 GB
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


_ATTN = ("ln1", "ln1_out", "ln2", "ln2_out", "q_norm", "k_norm", "wq", "wk",
         "wv", "wg", "wo")


def _layer_weights(weights, z, i):
  """Layer ``i``'s (0-based) leaves out of the stacks, as stored; the
  routed experts' stacks stay whole, with the layer's index in them under
  ``exp_at`` (``_experts`` takes one expert's matrices at a time)."""
  picks = {n: i for n in _ATTN}
  if i < z["dense_layers"]:
    picks.update({n: i for n in weights if n.startswith("mlp_")})
    return {n: weights[n][j] for n, j in picks.items()}
  j = i - z["dense_layers"]
  picks.update({n: j for n in weights if n.startswith(("router", "shared_"))})
  w = {n: weights[n][k] for n, k in picks.items()}
  w.update({n: weights[n] for n in weights if n.startswith("exp_")}, exp_at=j)
  return w


def _layer(x, w, z, i, precision, routing=None):
  """Layer ``i`` over ``x [B, S, D]``; ``w`` its own leaves as stored."""
  a = _rms_norm(x, w["ln1"], z["eps"])
  x = x + _rms_norm(_attention(a, w, z, i, precision), w["ln1_out"], z["eps"])
  c = _rms_norm(x, w["ln2"], z["eps"])
  if i < z["dense_layers"]:
    f = _swiglu(c, w["mlp_gate"], w["mlp_up"], w["mlp_down"], precision)
  else:
    if routing is not None:
      routing.append(route(c, w, z)[0])
    f = _experts(c, w, z, precision)
  return x + _rms_norm(f, w["ln2_out"], z["eps"])


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
    x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32) \
        * z["embed_scale"]
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
      num_kv_heads=z["kv_heads"], attn_head_dim=z["head_dim"],
      d_model=z["d_model"], d_ff=z["d_ff"], max_seq_len=int(max_seq_len),
      remat=False,
      dtype=jnp.dtype(config.get("compute_dtype", "bfloat16")),
      ffn_types=tuple("mlp" if i < z["dense_layers"] else "experts"
                      for i in range(z["layers"])),
      layer_windows=tuple(z["window"] if s else 0 for s in z["sliding"]),
      layer_rope=z["sliding"], qk_norm=True, attn_gate=True,
      embed_scale=z["embed_scale"], rope_theta=z["theta"], post_norm=True,
      norm="rms", norm_eps=z["eps"], mlp_act="swiglu", tie_embeddings=False,
      experts_total=z["routed"], experts_held=z["held"],
      experts_first=z["first"], experts_top_k=z["top_k"],
      experts_d_ff=z["expert_ff"], experts_shared=z["shared"],
      experts_scale=z["scale"])
  kw.update(overrides)
  return tfm.TransformerConfig(**kw)


def _to_program_tree(w, z):
  d, h, hk, dh = z["d_model"], z["heads"], z["kv_heads"], z["head_dim"]
  tree = {"embed": {"embedding": w["embed"]}, "head": {"kernel": w["head"]},
          "ln_f": {"scale": w["ln_f"]}}
  for i in range(z["layers"]):
    layer = {n: {"scale": w[n][i]}
             for n in ("ln1", "ln1_out", "ln2", "ln2_out")}
    layer["attn"] = {
        "q": {"kernel": w["wq"][i].reshape(d, h, dh)},
        "k": {"kernel": w["wk"][i].reshape(d, hk, dh)},
        "v": {"kernel": w["wv"][i].reshape(d, hk, dh)},
        "gate": {"kernel": w["wg"][i].reshape(d, h, dh)},
        "q_norm": {"scale": w["q_norm"][i]},
        "k_norm": {"scale": w["k_norm"][i]},
        "out": {"kernel": w["wo"][i].reshape(h, dh, d)}}
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
