"""The ``ouro`` family: how an Ouro configuration file becomes (a) the
benchmark's own plain reference and (b) the program's ``TransformerConfig``
and parameter tree.

Two halves, kept apart on purpose (as ``families/gpt2.py``):

* **the reference half** (``make_weights``, ``reference_logits``,
  ``reference_layer``, ``exit_distribution``) imports nothing of the program.
  It is the looped language model of arXiv:2510.25741 in straightforward
  ``jax.numpy``, a FULL forward pass with no cache: the ``num_hidden_layers``
  layers run ``total_ut_steps`` times over the SAME weights; a layer is
  ``x += RMSNorm(Attn(RMSNorm(x))); x += RMSNorm(MLP(RMSNorm(x)))`` (a norm
  before AND after each branch), attention with half-split rotary positions
  (the token's position, the same in every pass) and a plain causal softmax,
  the MLP gated SiLU; after the last layer of EVERY pass the one final RMSNorm,
  whose output enters the next pass, and an exit gate ``sigmoid(w . x + b)``;
  a token's logits are the untied head over the stream of its EXIT pass
  (``exit_distribution``: the last pass at the published threshold 1.0). In
  pass ``u`` a layer attends the keys and values of pass ``u`` alone: a cached
  decode equals this only with a cache a pass. float32 with
  ``jax.default_matmul_precision("highest")``; the layers' weights are stacked
  and upcast one layer at a time inside a scan, so the reference of the whole
  model fits beside its bf16 weights. ``precision`` swaps the matrix
  multiplications' inputs to a lower precision: the CONTROL of the ``correct``
  decision, never a speed-up.
* **the program half** (``program_config``, ``program_params``) is the only
  place that touches ``tensorflowonspark_tpu``.

``tests/ouro_family.py`` is a byte-for-byte copy of this file
(``benchmarks/tests/test_ouro.py`` keeps them equal), so that tier-1 tests
need nothing of ``benchmarks/``.
"""

import math

EMBED_STD = 0.02
PRECISIONS = ("f32", "bf16", "fp8")


def sizes(config: dict) -> dict:
  """The model's sizes from a ``config.json``-style dict (Hugging Face
  ``ouro`` key names)."""
  z = dict(
      vocab=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
      layers=int(config["num_hidden_layers"]),
      heads=int(config["num_attention_heads"]),
      head_dim=int(config["head_dim"]), d_ff=int(config["intermediate_size"]),
      eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]),
      passes=int(config["total_ut_steps"]),
      threshold=float(config["early_exit_threshold"]))
  if int(config.get("num_key_value_heads", z["heads"])) != z["heads"]:
    raise ValueError("grouped keys and values are not this family's")
  if config.get("rope_scaling") is not None \
      or config.get("sliding_window") is not None:
    raise ValueError("scaled rotary positions and windows are not this "
                     "family's")
  if any(t != "full_attention" for t in config.get("layer_types", ())):
    raise ValueError("this family's layers are all full attention")
  if config.get("hidden_act", "silu") != "silu" \
      or config.get("tie_word_embeddings", False):
    raise ValueError("this family's MLP is gated SiLU and its head untied")
  return z


def _leaves(z):
  """``(name, shape, kind)``: ``kind`` an int = fan-in of a normal matrix,
  or the name of a special draw."""
  d, n, f = z["d_model"], z["layers"], z["d_ff"]
  w = z["heads"] * z["head_dim"]
  return (
      ("embed", (z["vocab"], d), "embed"), ("head", (d, z["vocab"]), d),
      ("ln_f", (d,), "ones"), ("gate_w", (d,), "gate"),
      ("gate_b", (), "zero"),
      ("ln1", (n, d), "ones"), ("ln1_out", (n, d), "ones"),
      ("ln2", (n, d), "ones"), ("ln2_out", (n, d), "ones"),
      ("wq", (n, d, w), d), ("wk", (n, d, w), d), ("wv", (n, d, w), d),
      ("wo", (n, w, d), w),
      ("w_gate", (n, d, f), d), ("w_up", (n, d, f), d),
      ("w_down", (n, f, d), f),
  )


def param_count(config: dict) -> int:
  return sum(math.prod(shape) for _, shape, _ in _leaves(sizes(config)))


#: leaves kept in float32 whatever the matrices are stored in: norm scales
#: and the exit gate (a 2048 -> 1 map whose output is compared with a
#: threshold)
_F32 = ("ones", "gate", "zero")


def _weights_impl(key, z, dtype):
  import jax
  import jax.numpy as jnp
  out = {}
  for i, (name, shape, kind) in enumerate(_leaves(z)):
    k = jax.random.fold_in(key, i)
    if kind == "ones":
      w = jnp.ones(shape, jnp.float32)
    elif kind == "zero":
      w = jnp.zeros(shape, jnp.float32)
    elif kind == "embed":
      w = jax.random.normal(k, shape, jnp.float32) * EMBED_STD
    elif kind == "gate":
      w = jax.random.normal(k, shape, jnp.float32) / math.sqrt(shape[0])
    elif len(shape) > 2:
      # a stack over layers, drawn and rounded a layer at a time: drawn
      # whole, the float32 normals of a stack (48 x 2048 x 5632) are 2.2 GB
      # of scratch that the process then counts at its peak
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


def _rotate(x, theta):
  """Half-split rotary positions over ``x [B, S, H, D]``: the pair is
  ``(x[i], x[i + D/2])``, the angle ``position x theta^(-2i/D)``."""
  import jax.numpy as jnp
  half = x.shape[-1] // 2
  freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
  angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
  cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
  a, b = x[..., :half], x[..., half:]
  return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _layer(x, w, z, precision):
  """One layer over ``x [B, S, D]``; ``w`` its own leaves, float32."""
  import jax
  import jax.numpy as jnp
  b, s, _ = x.shape
  h, dh = z["heads"], z["head_dim"]
  a = _rms_norm(x, w["ln1"], z["eps"])
  q, k, v = (_mm("bsd,dw->bsw", a, w[n], precision).reshape(b, s, h, dh)
             for n in ("wq", "wk", "wv"))
  q, k = _rotate(q, z["theta"]), _rotate(k, z["theta"])
  scores = _mm("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(dh)
  causal = jnp.tril(jnp.ones((s, s), bool))
  probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -1e30), -1)
  o = _mm("bhqk,bkhd->bqhd", probs, v, precision).reshape(b, s, h * dh)
  x = x + _rms_norm(_mm("bsw,wd->bsd", o, w["wo"], precision),
                    w["ln1_out"], z["eps"])
  c = _rms_norm(x, w["ln2"], z["eps"])
  g = _mm("bsd,df->bsf", c, w["w_gate"], precision)
  hidden = g * jax.nn.sigmoid(g) * _mm("bsd,df->bsf", c, w["w_up"], precision)
  return x + _rms_norm(_mm("bsf,fd->bsd", hidden, w["w_down"], precision),
                       w["ln2_out"], z["eps"])


_PER_LAYER = ("ln1", "ln1_out", "ln2", "ln2_out", "wq", "wk", "wv", "wo",
              "w_gate", "w_up", "w_down")


def reference_layer(weights, x, config: dict, i: int,
                    precision: str = "f32"):
  """Layer ``i`` (0-based) of the model over ``x [B, S, D]``."""
  import jax
  import jax.numpy as jnp
  z = sizes(config)
  with jax.default_matmul_precision("highest"):
    return _layer(x, {n: weights[n][i].astype(jnp.float32)
                      for n in _PER_LAYER}, z, precision)


def exit_distribution(gates, threshold: float):
  """``(p, exit_pass)`` from the exit gates ``gates [passes, ...]`` (the
  last pass's gate is not used: it takes what is left): ``p[u] = gate[u]
  prod_{j<u}(1 - gate[j])``, ``p[last] = prod_{j<last}(1 - gate[j])``, so
  ``p`` sums to 1 over the passes; ``exit_pass`` (1-based) is the first pass
  whose cumulative ``p`` reaches ``threshold``, the last pass where none
  before it does: at 1.0 that is the last for every finite gate."""
  import jax.numpy as jnp
  gates = jnp.asarray(gates, jnp.float32)
  stay, p = jnp.ones_like(gates[0]), []          # stay: prod_{j<u}(1 - gate_j)
  for g in gates[:-1]:
    p.append(g * stay)
    stay = stay * (1.0 - g)
  p = jnp.stack(p + [stay])
  # the last pass's cumulative p is 1 but for rounding: it always exits
  reached = (jnp.cumsum(p, axis=0) >= threshold).at[-1].set(True)
  return p, jnp.argmax(reached, axis=0).astype(jnp.int32) + 1


def reference_logits(weights, tokens, config: dict, precision: str = "f32"):
  """Logits ``[B, S, V]`` (float32) of the plain model over ``tokens``."""
  import jax
  import jax.numpy as jnp
  z = sizes(config)
  stacked = {n: weights[n] for n in _PER_LAYER}

  def one(x, w):        # a layer's leaves upcast here, one layer at a time
    return _layer(x, {n: a.astype(jnp.float32) for n, a in w.items()}, z,
                  precision), None

  with jax.default_matmul_precision("highest"):
    x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    ends, gates = [], []
    for _ in range(z["passes"]):
      x, _ = jax.lax.scan(one, x, stacked)
      x = _rms_norm(x, weights["ln_f"].astype(jnp.float32), z["eps"])
      ends.append(x)
      gates.append(jax.nn.sigmoid(
          jnp.einsum("bsd,d->bs", x, weights["gate_w"].astype(jnp.float32))
          + weights["gate_b"]))
    _, leaves = exit_distribution(jnp.stack(gates), z["threshold"])
    out = ends[-1]
    for u, x in enumerate(ends[:-1]):
      out = jnp.where((leaves == u + 1)[..., None], x, out)
    return _mm("bsd,dv->bsv", out, weights["head"], precision)


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
      norm="rms", norm_eps=z["eps"], mlp_act="swiglu", tie_embeddings=False,
      attn_head_dim=z["head_dim"], rope_theta=z["theta"], post_norm=True,
      loop_passes=z["passes"], loop_exit_threshold=z["threshold"])
  kw.update(overrides)
  return tfm.TransformerConfig(**kw)


def _to_program_tree(w, z):
  d, h, dh = z["d_model"], z["heads"], z["head_dim"]
  tree = {"embed": {"embedding": w["embed"]}, "head": {"kernel": w["head"]},
          "ln_f": {"scale": w["ln_f"]},
          "exit_gate": {"kernel": w["gate_w"][:, None],
                        "bias": w["gate_b"][None]}}
  for i in range(z["layers"]):
    layer = {n: {"scale": w[n][i]}
             for n in ("ln1", "ln1_out", "ln2", "ln2_out")}
    layer["attn"] = {
        "q": {"kernel": w["wq"][i].reshape(d, h, dh)},
        "k": {"kernel": w["wk"][i].reshape(d, h, dh)},
        "v": {"kernel": w["wv"][i].reshape(d, h, dh)},
        "out": {"kernel": w["wo"][i].reshape(h, dh, d)}}
    layer["mlp"] = {n: {"kernel": w["w_" + n][i]}
                    for n in ("gate", "up", "down")}
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
