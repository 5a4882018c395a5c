"""The ``mimo_v2_flash`` family: how a ``mimo_v2_flash`` configuration file
(XiaomiMiMo's MiMo-V2-Flash) becomes (a) the benchmark's own plain reference
and (b) the program's ``TransformerConfig`` and parameter tree.

Two halves, kept apart on purpose (as ``families/gpt2.py``):

* **the reference half** (``make_weights``, ``reference_logits``,
  ``reference_layer``, ``route``) imports nothing of the program. It is the
  model in straightforward ``jax.numpy``, a FULL forward pass with no cache,
  no ring and no log-sum-exp trick: ``x = E[tokens]``; a layer is ``x +=
  Attn(RMSNorm(x)) Wo; x += FFN(RMSNorm(x))`` (pre-norm only); a layer is of
  one of two KINDS, full or window (``hybrid_layer_pattern``: 0 | 1), and the
  kind sets its KV head count (``num_key_value_heads`` |
  ``swa_num_key_value_heads``) and its rotary base (``rope_theta`` |
  ``swa_rope_theta``); every head has keys of ``head_dim`` and values of
  ``v_head_dim``, the values multiplied by ``attention_value_scale``; the
  first ``int(head_dim x partial_rotary_factor)`` dims of every query and key
  head rotate (half-split inside that part, the token's position), the rest
  pass; query head ``h`` reads KV head ``h // g``; scores ``q . k /
  sqrt(head_dim)``; a full layer attends every ``j <= i``; a window layer
  attends ``i - window < j <= i``, written as a MASK over the whole
  sequence, with ONE learned scalar a query head, the SINK, as an extra
  COLUMN of the softmax that is dropped after it (it joins the denominator and
  nothing else); the feed-forward is a dense gated-SiLU MLP (``moe_layer_freq``
  0) or sparse experts: ``s = sigmoid(c W_r)`` in float32, the ``k`` largest of
  ``s + b`` chosen (the bias for the selection only), weights ``s_e / (sum of
  the chosen s + 1e-20)``, NO shared expert; final RMSNorm, untied head.
  float32 with ``jax.default_matmul_precision("highest")``; attention runs a
  block of queries at a time, a feed-forward a block of tokens at a time, and
  the held experts are upcast and applied ONE AT A TIME, so that 16384
  positions fit beside the bf16 weights. ``precision`` swaps the matrix
  multiplications' inputs to a lower precision (``"fp8"``), or leaves every
  SINK out (``"no_sink"``): the CONTROLS of the ``correct`` decision, never a
  speed-up.
* **the program half** (``program_config``, ``program_params``) is the only
  place that touches ``tensorflowonspark_tpu``.

The configuration is ONE CHIP'S SHARE of an expert-parallel deployment and a
CUT IN DEPTH: ``n_routed_experts`` counts the experts HELD here
(``experts_first`` the first of them), ``n_routed_experts_published`` is the
router's width; the first ``num_hidden_layers`` entries of
``hybrid_layer_pattern`` and ``moe_layer_freq`` (copied whole) are the layers
kept. The reference gets the same share: an assignment to an expert held
elsewhere adds nothing, in both. ``tests/mimo_v2_flash_family.py`` is a
byte-for-byte copy of this file (``benchmarks/tests/test_mimo_v2_flash.py``
keeps them equal), so that tier-1 tests need nothing of ``benchmarks/``.
"""

import math

EMBED_STD = 0.02
ROUTE_EPS = 1e-20
#: the sinks are drawn N(SINK_MEAN, 1): at random weights a window's 128 scores
#: put about 128 x e^0.5 = 211 into the denominator, so exp(4) = 55 takes
#: about a fifth of the mass, as a trained sink takes a large share; drawn
#: near 0 it would take under 1% and no check could see it dropped
SINK_MEAN = 4.0
#: queries a block of the reference's attention (scores of 64 heads x 128 x
#: 16384 keys are 0.5 GB in float32)
QUERY_BLOCK = 128
#: tokens a block of the reference's feed-forward
TOKEN_BLOCK = 2048
PRECISIONS = ("f32", "bf16", "fp8", "no_sink")


def sizes(config: dict) -> dict:
  """The model's sizes from a ``config.json``-style dict (Hugging Face
  ``mimo_v2_flash`` key names, plus the cut's keys)."""
  layers = int(config["num_hidden_layers"])
  window = tuple(bool(k) for k in config["hybrid_layer_pattern"][:layers])
  sparse = tuple(bool(k) for k in config["moe_layer_freq"][:layers])
  if len(window) != layers or len(sparse) != layers:
    raise ValueError("hybrid_layer_pattern and moe_layer_freq must name "
                     "each of the %d layers kept" % layers)
  head_dim = int(config["head_dim"])
  z = dict(
      vocab=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
      layers=layers, window_layers=window, sparse=sparse,
      window=int(config["sliding_window"]),
      heads=int(config["num_attention_heads"]),
      kv_heads=tuple(int(config["swa_num_key_value_heads"]) if w
                     else int(config["num_key_value_heads"]) for w in window),
      theta=tuple(float(config["swa_rope_theta"]) if w
                  else float(config["rope_theta"]) for w in window),
      sink=tuple(bool(config["add_swa_attention_sink_bias"]) if w
                 else bool(config["add_full_attention_sink_bias"])
                 for w in window),
      head_dim=head_dim, v_dim=int(config["v_head_dim"]),
      rotary=int(head_dim * float(config["partial_rotary_factor"])),
      v_scale=float(config["attention_value_scale"]),
      d_ff=int(config["intermediate_size"]),
      expert_ff=int(config["moe_intermediate_size"]),
      held=int(config["n_routed_experts"]),
      first=int(config.get("experts_first", 0)),
      routed=int(config.get("n_routed_experts_published",
                            config["n_routed_experts"])),
      top_k=int(config["num_experts_per_tok"]),
      eps=float(config["layernorm_epsilon"]))
  for key, same in (("swa_num_attention_heads", "num_attention_heads"),
                    ("swa_head_dim", "head_dim"),
                    ("swa_v_head_dim", "v_head_dim")):
    if config.get(key, config[same]) != config[same]:
      raise ValueError("this family's window layers share %s with its full "
                       "layers" % same)
  if config.get("scoring_func", "sigmoid") != "sigmoid" \
      or not config.get("norm_topk_prob", True):
    raise ValueError("this family's router is a sigmoid with renormalised "
                     "weights")
  if config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
    raise ValueError("grouped routing limits are not this family's")
  if config.get("n_shared_experts") or config.get("routed_scaling_factor"):
    raise ValueError("this family has no shared expert and no further scale "
                     "on the routed ones")
  if config.get("hidden_act", "silu") != "silu" \
      or config.get("tie_word_embeddings", False) \
      or config.get("attention_bias", False):
    raise ValueError("this family's MLP is gated SiLU, its head untied and "
                     "its projections without bias")
  if z["rotary"] % 2 or any(z["heads"] % hk for hk in z["kv_heads"]):
    raise ValueError("the rotated part of a head is whole pairs, and KV "
                     "heads divide the query heads")
  return z


def _leaves(z):
  """``(name, shape, kind)``: ``kind`` an int = fan-in of a normal matrix,
  or the name of a special draw. The attention's K and V matrices are a stack
  a layer KIND (full layers in order, then window layers in order): the two
  kinds differ in width."""
  d, n, dk, dv, h = (z["d_model"], z["layers"], z["head_dim"], z["v_dim"],
                     z["heads"])
  ne = sum(z["sparse"])
  nd = n - ne
  f = z["expert_ff"]
  out = [
      ("embed", (z["vocab"], d), "embed"), ("head", (d, z["vocab"]), d),
      ("ln_f", (d,), "ones"), ("ln1", (n, d), "ones"), ("ln2", (n, d), "ones"),
      ("wq", (n, d, h * dk), d), ("wo", (n, h * dv, d), h * dv),
      ("sink", (n, h), "sink")]
  for kind, is_window in (("full", False), ("window", True)):
    at = [i for i, w in enumerate(z["window_layers"]) if w == is_window]
    if at:
      hk = z["kv_heads"][at[0]]
      out += [("wk_" + kind, (len(at), d, hk * dk), d),
              ("wv_" + kind, (len(at), d, hk * dv), d)]
  out += [
      ("mlp_gate", (nd, d, z["d_ff"]), d), ("mlp_up", (nd, d, z["d_ff"]), d),
      ("mlp_down", (nd, z["d_ff"], d), z["d_ff"]),
      ("router", (ne, d, z["routed"]), "router"),
      ("router_bias", (ne, z["routed"]), "router_bias"),
      ("exp_gate", (ne, z["held"], d, f), d),
      ("exp_up", (ne, z["held"], d, f), d),
      ("exp_down", (ne, z["held"], f, d), f)]
  return tuple(out)


def param_count(config: dict) -> int:
  """Parameters as built: a sink only where the layer's kind has one."""
  z = sizes(config)
  return sum(math.prod(shape) for name, shape, _ in _leaves(z)
             if name != "sink") + z["heads"] * sum(z["sink"])


#: leaves kept in float32 whatever the matrices are stored in: norm scales,
#: the router (its scores decide a top-k) and the sinks
_F32 = ("ones", "router", "router_bias", "sink")


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
    elif kind == "sink":
      w = SINK_MEAN + jax.random.normal(k, shape, jnp.float32)
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


def _lower(x, precision):
  """Matrix-multiplication inputs in the control's precision (``fp8``: the
  usual per-tensor-scaled e4m3 recipe; ``no_sink`` rounds nothing)."""
  import jax.numpy as jnp
  x = x.astype(jnp.float32)
  if precision in ("f32", "no_sink"):
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


def _rotate(x, positions, theta, rotary):
  """Half-split rotary positions over the FIRST ``rotary`` dims of ``x [B, S,
  H, D]`` at ``positions [S]``: the pair is ``(x[i], x[i + rotary/2])``, the
  angle ``position x theta^(-2i/rotary)``; dims from ``rotary`` on pass."""
  import jax.numpy as jnp
  half = rotary // 2
  freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
  angles = positions.astype(jnp.float32)[:, None] * freqs
  cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
  a, b, rest = x[..., :half], x[..., half:rotary], x[..., rotary:]
  return jnp.concatenate([a * cos - b * sin, a * sin + b * cos, rest],
                         axis=-1)


def _attention(a, w, z, i, precision):
  """The attention branch of layer ``i`` over its normed input ``a [B, S,
  D]``: keys and values of the whole sequence, then a block of queries at a
  time (its projection, its scores against every position with the window a
  MASK, the sink an extra column, its output projection), so that 64 heads x
  16384 x 16384 scores never exist at once."""
  import jax
  import jax.numpy as jnp
  b, s, _ = a.shape
  h, hk, dk, dv = z["heads"], z["kv_heads"][i], z["head_dim"], z["v_dim"]
  window = z["window"] if z["window_layers"][i] else 0
  theta = z["theta"][i]
  sink = z["sink"][i] and precision != "no_sink"
  k = _rotate(_mm("bsd,dw->bsw", a, w["wk"], precision).reshape(b, s, hk, dk),
              jnp.arange(s), theta, z["rotary"])
  v = z["v_scale"] * _mm("bsd,dw->bsw", a, w["wv"], precision).reshape(
      b, s, hk, dv)
  blk = math.gcd(s, QUERY_BLOCK)
  t = jnp.arange(s)

  def one(j):
    at = j * blk + jnp.arange(blk)
    a_j = jax.lax.dynamic_slice_in_dim(a, j * blk, blk, axis=1)
    q = _rotate(_mm("bsd,dw->bsw", a_j, w["wq"], precision).reshape(
        b, blk, h, dk), at, theta, z["rotary"])
    # query head h reads KV head h // g
    scores = _mm("bqkgd,btkd->bkgqt", q.reshape(b, blk, hk, h // hk, dk), k,
                 precision) / math.sqrt(dk)
    keep = t[None, :] <= at[:, None]
    if window:
      keep = jnp.logical_and(keep, t[None, :] > at[:, None] - window)
    scores = jnp.where(keep, scores, -1e30)
    if sink:
      # one more column a query head, the same for every query; dropped after
      # the softmax: its probability goes nowhere
      col = jnp.broadcast_to(
          w["sink"].astype(jnp.float32).reshape(1, hk, h // hk, 1, 1),
          scores.shape[:-1] + (1,))
      probs = jax.nn.softmax(jnp.concatenate([scores, col], axis=-1),
                             axis=-1)[..., :-1]
    else:
      probs = jax.nn.softmax(scores, axis=-1)
    o = _mm("bkgqt,btkd->bqkgd", probs, v, precision).reshape(b, blk, h * dv)
    return _mm("bsw,wd->bsd", o, w["wo"], precision)

  out = jax.lax.map(one, jnp.arange(s // blk))          # [S/blk, B, blk, D]
  return jnp.moveaxis(out, 0, 1).reshape(b, s, -1)


def _swiglu(x, gate, up, down, precision):
  """``(silu(x gate) * (x up)) down`` over ``x [B, S, D]``, a block of the
  sequence at a time (the hidden layer of 16384 x 16384 is 1.1 GB, thrice)."""
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
  return experts, picked / (jnp.sum(picked, -1, keepdims=True) + ROUTE_EPS)


def _experts(x, w, z, precision):
  """Every token through each HELD expert in turn, weighted by its routing
  (0 where the token did not choose it); experts held elsewhere add nothing.
  One expert's matrices are upcast at a time. No shared expert."""
  import jax
  import jax.numpy as jnp
  experts, weights = route(x, w, z)
  local = experts - z["first"]

  def matrix(name, e):
    # from the layer's stack [held, ...], or (``_layer_weights``) straight
    # from the model's [layers, held, ...]: a layer's stack is 0.8 GB
    stack = w[name]
    return stack[e] if stack.ndim == 3 else stack[w["exp_at"], e]

  def one(e, acc):
    mine = jnp.sum(jnp.where(local == e, weights, 0.0), axis=-1)   # [B, S]
    out = _swiglu(x, matrix("exp_gate", e), matrix("exp_up", e),
                  matrix("exp_down", e), precision)
    return acc + out * mine[..., None]

  return jax.lax.fori_loop(0, z["held"], one, jnp.zeros_like(x))


def _layer_weights(weights, z, i):
  """Layer ``i``'s (0-based) leaves out of the stacks, as stored; the routed
  experts' stacks stay whole, with the layer's index in them under
  ``exp_at`` (``_experts`` takes one expert's matrices at a time)."""
  kind = "window" if z["window_layers"][i] else "full"
  among = sum(1 for w in z["window_layers"][:i] if w == z["window_layers"][i])
  w = {n: weights[n][i] for n in ("ln1", "ln2", "wq", "wo", "sink")}
  w.update(wk=weights["wk_" + kind][among], wv=weights["wv_" + kind][among])
  j = sum(z["sparse"][:i])
  if not z["sparse"][i]:
    w.update({n: weights[n][i - j] for n in ("mlp_gate", "mlp_up",
                                             "mlp_down")})
    return w
  w.update({n: weights[n][j] for n in ("router", "router_bias")})
  w.update({n: weights[n] for n in ("exp_gate", "exp_up", "exp_down")},
           exp_at=j)
  return w


def _layer(x, w, z, i, precision, routing=None):
  """Layer ``i`` over ``x [B, S, D]``; ``w`` its own leaves as stored."""
  x = x + _attention(_rms_norm(x, w["ln1"], z["eps"]), w, z, i, precision)
  c = _rms_norm(x, w["ln2"], z["eps"])
  if not z["sparse"][i]:
    return x + _swiglu(c, w["mlp_gate"], w["mlp_up"], w["mlp_down"],
                       precision)
  if routing is not None:
    routing.append(route(c, w, z)[0])
  return x + _experts(c, w, z, precision)


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
      attn_head_dim=z["head_dim"], attn_v_head_dim=z["v_dim"],
      layer_kv_heads=z["kv_heads"], rope_dim=z["rotary"],
      layer_rope_theta=z["theta"], layer_sink=z["sink"],
      attn_value_scale=z["v_scale"],
      d_model=z["d_model"], d_ff=z["d_ff"], max_seq_len=int(max_seq_len),
      remat=False,
      dtype=jnp.dtype(config.get("compute_dtype", "bfloat16")),
      ffn_types=tuple("experts" if s else "mlp" for s in z["sparse"]),
      layer_windows=tuple(z["window"] if w else 0
                          for w in z["window_layers"]),
      norm="rms", norm_eps=z["eps"], mlp_act="swiglu", tie_embeddings=False,
      experts_total=z["routed"], experts_held=z["held"],
      experts_first=z["first"], experts_top_k=z["top_k"],
      experts_d_ff=z["expert_ff"], experts_shared=0, experts_scale=1.0)
  kw.update(overrides)
  return tfm.TransformerConfig(**kw)


def _to_program_tree(w, z):
  d, h, dk, dv = z["d_model"], z["heads"], z["head_dim"], z["v_dim"]
  tree = {"embed": {"embedding": w["embed"]}, "head": {"kernel": w["head"]},
          "ln_f": {"scale": w["ln_f"]}}
  for i in range(z["layers"]):
    lw = _layer_weights(w, z, i)
    hk = z["kv_heads"][i]
    layer = {n: {"scale": lw[n]} for n in ("ln1", "ln2")}
    layer["attn"] = {
        "q": {"kernel": lw["wq"].reshape(d, h, dk)},
        "k": {"kernel": lw["wk"].reshape(d, hk, dk)},
        "v": {"kernel": lw["wv"].reshape(d, hk, dv)},
        "out": {"kernel": lw["wo"].reshape(h, dv, d)}}
    if z["sink"][i]:
      layer["attn"]["sink"] = lw["sink"]
    if not z["sparse"][i]:
      layer["mlp"] = {n: {"kernel": lw["mlp_" + n]}
                      for n in ("gate", "up", "down")}
    else:
      j = lw["exp_at"]
      layer["moe"] = {"router": lw["router"],
                      "router_bias": lw["router_bias"],
                      "gate": w["exp_gate"][j], "up": w["exp_up"][j],
                      "down": w["exp_down"][j]}
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
