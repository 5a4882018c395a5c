"""The ``keye_vl2`` family: how a ``KeyeVL2`` configuration file (Kwai-Keye's
Keye-VL-2.0, its LANGUAGE MODEL) becomes (a) the benchmark's own plain
reference and (b) the program's ``TransformerConfig`` and parameter tree.

Two halves, kept apart on purpose (as ``families/trinity.py``):

* **the reference half** (``make_weights``, ``reference_logits``,
  ``reference_layer``, ``route``, ``index_scores``, ``select``) imports
  nothing of the program. It is the model in straightforward ``jax.numpy``, a
  FULL forward pass with no cache: ``x = E[tokens]``; a layer is ``y =
  RMSNorm(x); x += Attn(y); x += Experts(RMSNorm(x))``. Attention is
  grouped-query (query head ``i`` reads KV head ``i // g``) with an RMSNorm
  over each head's ``head_dim`` of the queries and of the keys, rotated
  (half-split pairs, ``rope_theta``; positions of THREE components rotate
  their ``mrope_section`` of the pairs each: text tokens carry three equal
  components, which is the plain rotation). A learned INDEXER chooses what a
  query attends: from the same normed input, ``indexer_num_heads`` queries of
  ``indexer_head_dim`` and ONE key a token (LayerNorm with scale and bias),
  both rotated over all their dims; ``I(t, s) = sum_h w_t,h relu(qI_t,h .
  kI_s)`` with ``w_t = y_t W_w heads^-0.5 dim^-0.5``; query ``t`` attends the
  ``min(t + 1, topk)`` positions ``s <= t`` with the largest ``I``, the
  earlier position first among equals (``lax.top_k``'s order), written as a
  MASK over the whole sequence. The feed-forward is sparse experts: ``p =
  softmax(z W_r)`` in float32, the ``k`` largest, weights ``p_e / sum of the
  chosen``, no bias, no shared expert; final RMSNorm, untied head. float32
  under ``jax.default_matmul_precision("highest")``; attention runs a block of
  queries at a time and the held experts are applied ONE AT A TIME over blocks
  of tokens, so that 32768 positions fit. ``precision`` swaps the matrix
  multiplications' inputs to a lower precision (``"fp8"``, ``"bf16"``) or
  changes ONE piece of the mathematics (``"no_select"``: every candidate is
  attended; ``"select_half"``: half of ``topk`` is chosen; ``"no_renorm"``:
  the router's weights are the chosen probabilities as they are): the
  CONTROLS of the ``correct`` decision, never a speed-up.
* **the program half** (``program_config``, ``program_params``) is the only
  place that touches ``tensorflowonspark_tpu``.

The configuration is ONE CHIP'S SHARE of an expert-parallel deployment and a
CUT IN DEPTH: ``num_experts`` counts the experts HELD here (``experts_first``
the first of them), ``num_experts_published`` is the router's width;
``num_hidden_layers`` layers are kept (every published layer is of one kind).
The reference gets the same share: an assignment to an expert held elsewhere
adds nothing, in both. The vision tower is LEFT OUT (the configuration file
says why). ``tests/keye_vl2_family.py`` is a byte-for-byte copy of this file
(``benchmarks/tests/test_keye_vl2.py`` keeps them equal), so that tier-1 tests
need nothing of ``benchmarks/``.
"""

import math

EMBED_STD = 0.02
#: queries a block of the reference's attention (scores of 32 heads x 128 x
#: 32768 keys are 0.5 GB in float32, the 16 index heads' half of that)
QUERY_BLOCK = 128
#: tokens a block of the reference's feed-forward
TOKEN_BLOCK = 4096
PRECISIONS = ("f32", "bf16", "fp8", "no_select", "select_half", "no_renorm")
#: controls that change the mathematics and leave every number in float32
_MATH = ("no_select", "select_half", "no_renorm")


def sizes(config: dict) -> dict:
  """The model's sizes from a ``config.json``-style dict (Hugging Face
  ``KeyeVL2`` key names, plus the cut's keys)."""
  sa = config["sa_config"]
  z = dict(
      vocab=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
      layers=int(config["num_hidden_layers"]),
      heads=int(config["num_attention_heads"]),
      kv_heads=int(config["num_key_value_heads"]),
      head_dim=int(config["head_dim"]),
      expert_ff=int(config["moe_intermediate_size"]),
      held=int(config["num_experts"]),
      first=int(config.get("experts_first", 0)),
      routed=int(config.get("num_experts_published", config["num_experts"])),
      top_k=int(config["num_experts_per_tok"]),
      eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]),
      sections=tuple(int(n) for n in
                     config["rope_scaling"]["mrope_section"]),
      index_heads=int(sa["indexer_num_heads"]),
      index_dim=int(sa["indexer_head_dim"]), topk=int(sa["topk"]))
  if int(sa["indexer_num_kv_heads"]) != 1:
    raise ValueError("this family's indexer has ONE key a token")
  if not config.get("norm_topk_prob", True):
    raise ValueError("this family's router renormalises the chosen weights")
  if int(config.get("decoder_sparse_step", 1)) != 1 \
      or config.get("mlp_only_layers"):
    raise ValueError("every layer of this family is sparse")
  if config.get("attention_bias", False) \
      or config.get("hidden_act", "silu") != "silu" \
      or config.get("tie_word_embeddings", False):
    raise ValueError("this family has no attention bias, gated SiLU experts "
                     "and an untied head")
  if config.get("use_sliding_window", False):
    raise ValueError("sliding windows are not this family's")
  if z["heads"] % z["kv_heads"] or sum(z["sections"]) * 2 != z["head_dim"]:
    raise ValueError("KV heads must divide the query heads and the rotary "
                     "sections cover a head's pairs")
  if not z["first"] + z["held"] <= z["routed"]:
    raise ValueError("the held experts lie inside the router's width")
  return z


def _leaves(z):
  """``(name, shape, kind)``: ``kind`` an int = fan-in of a normal matrix,
  or the name of a special draw."""
  d, n, dh = z["d_model"], z["layers"], z["head_dim"]
  wq, wkv = z["heads"] * dh, z["kv_heads"] * dh
  hi, di, f = z["index_heads"], z["index_dim"], z["expert_ff"]
  return (
      ("embed", (z["vocab"], d), "embed"), ("head", (d, z["vocab"]), d),
      ("ln_f", (d,), "ones"),
      ("ln1", (n, d), "ones"), ("ln2", (n, d), "ones"),
      ("q_norm", (n, dh), "ones"), ("k_norm", (n, dh), "ones"),
      ("wq", (n, d, wq), d), ("wk", (n, d, wkv), d), ("wv", (n, d, wkv), d),
      ("wo", (n, wq, d), wq),
      # the indexer: queries, the one key a token with its LayerNorm, weights
      ("iq", (n, d, hi * di), d), ("ik", (n, d, di), d),
      ("ik_scale", (n, di), "ones"), ("ik_bias", (n, di), "zeros"),
      ("iw", (n, d, hi), d),
      ("router", (n, d, z["routed"]), "router"),
      ("exp_gate", (n, z["held"], d, f), d),
      ("exp_up", (n, z["held"], d, f), d),
      ("exp_down", (n, z["held"], f, d), f),
  )


def param_count(config: dict) -> int:
  return sum(math.prod(shape) for _, shape, _ in _leaves(sizes(config)))


#: leaves kept in float32 whatever the matrices are stored in: norm scales
#: and biases, and the router (its scores decide a top-k)
_F32 = ("ones", "zeros", "router")


def _weights_impl(key, z, dtype):
  import jax
  import jax.numpy as jnp
  out = {}
  for i, (name, shape, kind) in enumerate(_leaves(z)):
    k = jax.random.fold_in(key, i)
    if kind == "ones":
      w = jnp.ones(shape, jnp.float32)
    elif kind == "zeros":
      w = jnp.zeros(shape, jnp.float32)
    elif kind == "embed":
      w = jax.random.normal(k, shape, jnp.float32) * EMBED_STD
    elif kind == "router":
      w = jax.random.normal(k, shape, jnp.float32) / math.sqrt(shape[1])
    elif len(shape) > 2:
      # a stack over layers (and experts), drawn and rounded a matrix at a
      # time, so that the float32 normals of a stack are never whole
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
  if precision == "f32" or precision in _MATH:
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


def _layer_norm(x, scale, bias, eps):
  import jax.numpy as jnp
  mean = jnp.mean(x, axis=-1, keepdims=True)
  var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
  return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _silu(x):
  import jax
  return x * jax.nn.sigmoid(x)


def rotate(x, positions, theta, sections=None):
  """Half-split rotary positions over ``x [B, S, H, D]``: the pair is
  ``(x[i], x[i + D/2])``, the angle ``position x theta^(-2i/D)``.
  ``positions [S]`` is one component a token; ``positions [3, S]`` with
  ``sections`` (``mrope_section``: how many of the ``D/2`` pairs each
  component rotates, in order) is the SECTIONED rotation of a model whose
  inputs carry temporal, height and width positions. Three equal components
  are the one-component rotation, pair for pair."""
  import jax.numpy as jnp
  half = x.shape[-1] // 2
  freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
  if positions.ndim == 2:
    which = jnp.repeat(jnp.arange(len(sections)), jnp.asarray(sections),
                       total_repeat_length=half)              # [half]
    pos = positions.astype(jnp.float32)[which]                # [half, S]
    angles = pos.T * freqs
  else:
    angles = positions.astype(jnp.float32)[:, None] * freqs
  cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
  a, b = x[..., :half], x[..., half:]
  return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def index_scores(qi, ki, wi, precision="f32"):
  """``I [B, Q, S]``: ``qi [B, Q, H, D]`` and ``ki [B, S, D]`` rotated, ``wi
  [B, Q, H]`` scaled: ``sum_h wi_h relu(qi_h . ki_s)``."""
  import jax
  import jax.numpy as jnp
  s = _mm("bqhd,bsd->bqhs", qi, ki, precision)
  return jnp.sum(jax.nn.relu(s) * wi[..., None], axis=2)


def select(scores, at, topk: int):
  """The mask ``[B, Q, S]`` of what each query attends: ``scores [B, Q, S]``,
  query ``q`` at position ``at[q]``; its candidates are the positions ``s <=
  at[q]``, kept are the ``min(at[q] + 1, topk)`` with the largest score, the
  earlier position first among equal scores. The ``topk``-th largest comes
  from ``lax.top_k``; what equals it is kept in order of position."""
  import jax
  import jax.numpy as jnp
  s = scores.shape[-1]
  cand = jnp.arange(s)[None, :] <= at[:, None]                    # [Q, S]
  masked = jnp.where(cand, scores, -jnp.inf)
  k = min(int(topk), s)
  kth = jax.lax.top_k(masked, k)[0][..., -1:]          # -inf: fewer than k
  above = masked > kth
  equal = jnp.logical_and(masked == kth, cand)
  need = k - jnp.sum(above, axis=-1, keepdims=True)
  return jnp.logical_or(above, jnp.logical_and(
      equal, jnp.cumsum(equal, axis=-1) <= need))


def _attention(a, w, z, precision):
  """The attention branch over the layer's normed input ``a [B, S, D]``: keys,
  values and index keys of the whole sequence, then a block of queries at a
  time (its projections, its index scores and selection, its scores against
  every position under the selection's MASK, its output projection)."""
  import jax
  import jax.numpy as jnp
  b, s, _ = a.shape
  h, hk, dh = z["heads"], z["kv_heads"], z["head_dim"]
  hi, di = z["index_heads"], z["index_dim"]
  topk = {"no_select": s, "select_half": z["topk"] // 2}.get(
      precision, z["topk"])
  pos = jnp.arange(s)
  pos3 = jnp.stack([pos] * 3)          # text: three equal components
  k = rotate(_rms_norm(_mm("bsd,dw->bsw", a, w["wk"], precision).reshape(
      b, s, hk, dh), w["k_norm"], z["eps"]), pos3, z["theta"], z["sections"])
  v = _mm("bsd,dw->bsw", a, w["wv"], precision).reshape(b, s, hk, dh)
  ki = rotate(_layer_norm(_mm("bsd,dw->bsw", a, w["ik"], precision),
                          w["ik_scale"], w["ik_bias"],
                          z["eps"])[:, :, None, :], pos, z["theta"])[:, :, 0]
  blk = math.gcd(s, QUERY_BLOCK)

  def one(j):
    at = j * blk + jnp.arange(blk)
    a_j = jax.lax.dynamic_slice_in_dim(a, j * blk, blk, axis=1)
    q = rotate(_rms_norm(_mm("bsd,dw->bsw", a_j, w["wq"], precision).reshape(
        b, blk, h, dh), w["q_norm"], z["eps"]), jnp.stack([at] * 3),
               z["theta"], z["sections"])
    qi = rotate(_mm("bsd,dw->bsw", a_j, w["iq"], precision).reshape(
        b, blk, hi, di), at, z["theta"])
    wi = _mm("bsd,dw->bsw", a_j, w["iw"], precision) \
        * (hi ** -0.5 * di ** -0.5)
    keep = select(index_scores(qi, ki, wi, precision), at, topk)
    # query head h reads KV head h // g
    scores = _mm("bqkgd,btkd->bkgqt", q.reshape(b, blk, hk, h // hk, dh), k,
                 precision) / math.sqrt(dh)
    probs = jax.nn.softmax(
        jnp.where(keep[:, None, None], scores, -1e30), axis=-1)
    o = _mm("bkgqt,btkd->bqkgd", probs, v, precision).reshape(b, blk, h * dh)
    return _mm("bsw,wd->bsd", o, w["wo"], precision)

  out = jax.lax.map(one, jnp.arange(s // blk))          # [S/blk, B, blk, D]
  return jnp.moveaxis(out, 0, 1).reshape(b, s, -1)


def chosen_rows(weights, x, config: dict, i: int):
  """The selection of layer ``i`` over its INPUT ``x [B, S, D]`` (the stream
  before the layer): the mask ``[B, S, S]`` bool, float32. For tests and
  ``benchmarks/tools/selection_overlap.py``."""
  import jax
  import jax.numpy as jnp
  z = sizes(config)
  w = _layer_weights(weights, z, i)
  hi, di = z["index_heads"], z["index_dim"]
  with jax.default_matmul_precision("highest"):
    a = _rms_norm(x.astype(jnp.float32), w["ln1"], z["eps"])
    b, s, _ = a.shape
    pos = jnp.arange(s)
    ki = rotate(_layer_norm(_mm("bsd,dw->bsw", a, w["ik"], "f32"),
                            w["ik_scale"], w["ik_bias"],
                            z["eps"])[:, :, None, :], pos, z["theta"])[:, :, 0]
    qi = rotate(_mm("bsd,dw->bsw", a, w["iq"], "f32").reshape(b, s, hi, di),
                pos, z["theta"])
    wi = _mm("bsd,dw->bsw", a, w["iw"], "f32") * (hi ** -0.5 * di ** -0.5)
    blk = math.gcd(s, QUERY_BLOCK)

    def one(j):            # a block of queries: [B, blk, heads, S] at a time
      at = j * blk + jnp.arange(blk)
      cut = lambda x: jax.lax.dynamic_slice_in_dim(x, j * blk, blk, axis=1)  # noqa: E731
      return select(index_scores(cut(qi), ki, cut(wi)), at, z["topk"])

    out = jax.lax.map(one, jnp.arange(s // blk))        # [S/blk, B, blk, S]
    return jnp.moveaxis(out, 0, 1).reshape(b, s, s)


def _swiglu(x, gate, up, down, precision):
  """``(silu(x gate) * (x up)) down`` over ``x [B, S, D]``, a block of the
  sequence at a time."""
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


def route(x, w, z, renorm: bool = True):
  """``(experts [B, S, k], weights [B, S, k])``: float32 whatever the
  control's precision (a router in fp8 is another model, not a rounding).
  ``renorm`` false leaves the chosen probabilities as they are (a control)."""
  import jax
  import jax.numpy as jnp
  p = jax.nn.softmax(jnp.einsum("bsd,de->bse", x,
                                w["router"].astype(jnp.float32)), axis=-1)
  picked, experts = jax.lax.top_k(p, z["top_k"])
  if renorm:
    picked = picked / jnp.sum(picked, -1, keepdims=True)
  return experts, picked


def _experts(x, w, z, precision):
  """Every token through each HELD expert in turn, weighted by its routing
  (0 where the token did not choose it); experts held elsewhere add nothing."""
  import jax
  import jax.numpy as jnp
  experts, weights = route(x, w, z, precision != "no_renorm")
  local = experts - z["first"]

  def one(e, acc):
    mine = jnp.sum(jnp.where(local == e, weights, 0.0), axis=-1)   # [B, S]
    out = _swiglu(x, w["exp_gate"][e], w["exp_up"][e], w["exp_down"][e],
                  precision)
    return acc + out * mine[..., None]

  return jax.lax.fori_loop(0, z["held"], one, jnp.zeros_like(x))


def _layer_weights(weights, z, i):
  """Layer ``i``'s (0-based) leaves out of the stacks, as stored."""
  return {n: weights[n][i] for n, shape, _ in _leaves(z)
          if len(shape) > 1 and shape[0] == z["layers"]
          and n not in ("embed", "head")}


def _layer(x, w, z, precision, routing=None):
  """One layer over ``x [B, S, D]``; ``w`` its own leaves as stored."""
  a = _rms_norm(x, w["ln1"], z["eps"])
  x = x + _attention(a, w, z, precision)
  c = _rms_norm(x, w["ln2"], z["eps"])
  if routing is not None:
    routing.append(route(c, w, z)[0])
  return x + _experts(c, w, z, precision)


def reference_layer(weights, x, config: dict, i: int,
                    precision: str = "f32", routing: list = None):
  """Layer ``i`` (0-based, of the layers kept) over ``x [B, S, D]``.
  ``routing`` (a list) collects the layer's expert choices ``[B, S, k]``."""
  import jax
  z = sizes(config)
  with jax.default_matmul_precision("highest"):
    return _layer(x, _layer_weights(weights, z, i), z, precision, routing)


def reference_logits(weights, tokens, config: dict, precision: str = "f32",
                     routing: list = None, streams: list = None):
  """Logits ``[B, S, V]`` (float32) of the plain model over ``tokens``.
  ``streams`` (a list) collects each layer's INPUT ``[B, S, D]``."""
  import jax
  import jax.numpy as jnp
  z = sizes(config)
  if precision not in PRECISIONS:
    raise ValueError("precision must be one of %r, got %r"
                     % (PRECISIONS, precision))
  with jax.default_matmul_precision("highest"):
    x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    for i in range(z["layers"]):
      if streams is not None:
        streams.append(x)
      x = _layer(x, _layer_weights(weights, z, i), z, precision, routing)
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
      d_model=z["d_model"], d_ff=z["expert_ff"], max_seq_len=int(max_seq_len),
      remat=False,
      dtype=jnp.dtype(config.get("compute_dtype", "bfloat16")),
      ffn_types=("experts",) * z["layers"], qk_norm=True,
      rope_theta=z["theta"], norm="rms", norm_eps=z["eps"],
      mlp_act="swiglu", tie_embeddings=False,
      experts_total=z["routed"], experts_held=z["held"],
      experts_first=z["first"], experts_top_k=z["top_k"],
      experts_d_ff=z["expert_ff"], experts_shared=0,
      experts_score="softmax", sparse_topk=z["topk"],
      index_heads=z["index_heads"], index_head_dim=z["index_dim"],
      act_f32=bool(config.get("float32_activations", False)))
  kw.update(overrides)
  return tfm.TransformerConfig(**kw)


def _to_program_tree(w, z):
  d, h, hk, dh = z["d_model"], z["heads"], z["kv_heads"], z["head_dim"]
  hi, di = z["index_heads"], z["index_dim"]
  tree = {"embed": {"embedding": w["embed"]}, "head": {"kernel": w["head"]},
          "ln_f": {"scale": w["ln_f"]}}
  for i in range(z["layers"]):
    layer = {n: {"scale": w[n][i]} for n in ("ln1", "ln2")}
    layer["attn"] = {
        "q": {"kernel": w["wq"][i].reshape(d, h, dh)},
        "k": {"kernel": w["wk"][i].reshape(d, hk, dh)},
        "v": {"kernel": w["wv"][i].reshape(d, hk, dh)},
        "q_norm": {"scale": w["q_norm"][i]},
        "k_norm": {"scale": w["k_norm"][i]},
        "out": {"kernel": w["wo"][i].reshape(h, dh, d)},
        "index_q": {"kernel": w["iq"][i].reshape(d, hi, di)},
        "index_k": {"kernel": w["ik"][i]},
        "index_k_norm": {"scale": w["ik_scale"][i], "bias": w["ik_bias"][i]},
        "index_w": {"kernel": w["iw"][i]}}
    layer["moe"] = {"router": w["router"][i], "gate": w["exp_gate"][i],
                    "up": w["exp_up"][i], "down": w["exp_down"][i]}
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
