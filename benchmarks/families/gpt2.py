"""The ``gpt2`` family: how a GPT-2 configuration file becomes (a) the
program's ``TransformerConfig`` and parameter tree and (b) the benchmark's
own plain reference.

Two halves, kept apart on purpose:

* **the reference half** (``make_weights``, ``reference_logits``,
  ``reference_train``) imports nothing of the program.  It is the block in
  straightforward ``jax.numpy``: pre-LN decoder, LayerNorm without bias,
  multi-head attention with rotary positions over the whole head, ungated
  tanh-GELU MLP of 4x, tied head; float32 with
  ``jax.default_matmul_precision("highest")``.  No kernels, no cache, no
  batching tricks.  ``precision`` swaps the matrix multiplications' inputs to
  a lower precision: that is the CONTROL of the ``correct`` decision (a
  reference computed in fp8 has to fail it), never a speed-up.
* **the program half** (``program_config``, ``program_params``,
  ``program_loss_fn``, ``program_train_state``) is the only place that
  touches ``tensorflowonspark_tpu``.  Weights are made by THIS file from the
  seed, in one jitted call on the device, and handed to the program in its
  own tree layout; the reference gets the same numbers from the same call and
  nothing that the program has computed.

Sizes come from the configuration file (Hugging Face GPT-2 key names).
"""

import math

#: init scales this family gives both sides (the program's own initialisers
#: are lecun-normal kernels and a 0.02 embedding; the benchmark makes its own
#: weights to the same scales so losses sit where a user's would)
EMBED_STD = 0.02
LN_EPS = 1e-6          # the block's LayerNorm epsilon (GPT-2 publishes 1e-5)
ROPE_THETA = 10000.0
ADAMW = dict(lr=3e-4, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)


def sizes(config: dict) -> dict:
  """The block's sizes from a GPT-2 ``config.json``-style dict."""
  d = int(config["n_embd"])
  h = int(config["n_head"])
  if d % h:
    raise ValueError("n_embd %d does not divide by n_head %d" % (d, h))
  inner = config.get("n_inner") or 4 * d
  return dict(vocab=int(config["vocab_size"]), layers=int(config["n_layer"]),
              heads=h, d_model=d, head_dim=d // h, d_ff=int(inner),
              positions=int(config["n_positions"]))


def param_count(config: dict) -> int:
  z = sizes(config)
  per_layer = 4 * z["d_model"] * z["d_model"] + 2 * z["d_model"] * z["d_ff"] \
      + 2 * z["d_model"]
  return z["vocab"] * z["d_model"] + z["layers"] * per_layer + z["d_model"]


# ---------------------------------------------------------------------------
# weights: one jitted call from the seed, stacked over layers
# ---------------------------------------------------------------------------

#: leaf name -> (shape builder, fan-in builder); ``None`` fan-in = ones
_LEAVES = (
    ("embed", lambda z: (z["vocab"], z["d_model"]), "embed"),
    ("ln1", lambda z: (z["layers"], z["d_model"]), None),
    ("q", lambda z: (z["layers"], z["d_model"], z["heads"], z["head_dim"]),
     "d_model"),
    ("k", lambda z: (z["layers"], z["d_model"], z["heads"], z["head_dim"]),
     "d_model"),
    ("v", lambda z: (z["layers"], z["d_model"], z["heads"], z["head_dim"]),
     "d_model"),
    ("out", lambda z: (z["layers"], z["heads"], z["head_dim"], z["d_model"]),
     "d_model"),
    ("ln2", lambda z: (z["layers"], z["d_model"]), None),
    ("up", lambda z: (z["layers"], z["d_model"], z["d_ff"]), "d_model"),
    ("down", lambda z: (z["layers"], z["d_ff"], z["d_model"]), "d_ff"),
    ("ln_f", lambda z: (z["d_model"],), None),
)


def _weights_impl(key, z, dtype):
  import jax
  import jax.numpy as jnp
  out = {}
  for i, (name, shape, fan) in enumerate(_LEAVES):
    shp = shape(z)
    if fan is None:
      out[name] = jnp.ones(shp, jnp.float32)      # LayerNorm scales stay f32
      continue
    std = EMBED_STD if fan == "embed" else 1.0 / math.sqrt(z[fan])
    w = jax.random.normal(jax.random.fold_in(key, i), shp, jnp.float32) * std
    out[name] = w.astype(dtype)
  return out


def make_weights(seed: int, config: dict, dtype="float32"):
  """Stacked weights ``{leaf: array}`` from the seed, one jitted call on the
  default device.  ``dtype`` is the type the matrices are STORED in
  (``bfloat16`` for serving: the model then IS the rounded numbers, and the
  reference upcasts those same numbers)."""
  import jax
  import jax.numpy as jnp
  z = sizes(config)
  key = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))
  fn = jax.jit(lambda k: _weights_impl(k, z, jnp.dtype(dtype)))
  return fn(key)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

PRECISIONS = ("f32", "bf16", "fp8")


def _lower(x, precision):
  """Matrix-multiplication inputs in the control's precision.  ``fp8`` is
  the usual per-tensor-scaled e4m3 recipe; ``bf16`` is the precision the
  configurations state (used by the CPU tests as the sound stand-in).  The
  rounding is straight-through, so a gradient flows as fake-quantised
  training computes it."""
  import jax
  import jax.numpy as jnp
  x = x.astype(jnp.float32)
  if precision == "f32":
    return x
  if precision == "bf16":
    q = x.astype(jnp.bfloat16).astype(jnp.float32)
  elif precision == "fp8":
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
  else:
    raise ValueError("precision must be one of %r, got %r"
                     % (PRECISIONS, precision))
  return x + jax.lax.stop_gradient(q - x)


def _mm(spec, a, b, precision):
  import jax.numpy as jnp
  return jnp.einsum(spec, _lower(a, precision), _lower(b, precision))


def _layer_norm(x, scale):
  import jax.numpy as jnp
  mean = jnp.mean(x, axis=-1, keepdims=True)
  var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
  return (x - mean) / jnp.sqrt(var + LN_EPS) * scale


def _rotary(x, head_dim):
  """x: [B, S, H, hd]; rotary over the whole head, halves layout."""
  import jax.numpy as jnp
  half = head_dim // 2
  freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                  * (math.log(ROPE_THETA) / half))
  ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
  cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
  x1, x2 = x[..., :half], x[..., half:]
  return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _gelu_tanh(x):
  import jax.numpy as jnp
  return 0.5 * x * (1.0 + jnp.tanh(
      math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, w, z, precision):
  import jax
  import jax.numpy as jnp
  s = x.shape[1]
  y = _layer_norm(x, w["ln1"])
  q = _rotary(_mm("bsd,dhk->bshk", y, w["q"], precision), z["head_dim"])
  k = _rotary(_mm("bsd,dhk->bshk", y, w["k"], precision), z["head_dim"])
  v = _mm("bsd,dhk->bshk", y, w["v"], precision)
  scores = _mm("bqhk,bthk->bhqt", q, k, precision) / math.sqrt(z["head_dim"])
  causal = jnp.tril(jnp.ones((s, s), bool))
  probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -1e30), -1)
  att = _mm("bhqt,bthk->bqhk", probs, v, precision)
  x = x + _mm("bqhk,hkd->bqd", att, w["out"], precision)
  y = _layer_norm(x, w["ln2"])
  hidden = _gelu_tanh(_mm("bsd,df->bsf", y, w["up"], precision))
  return x + _mm("bsf,fd->bsd", hidden, w["down"], precision)


_LAYER_LEAVES = ("ln1", "q", "k", "v", "out", "ln2", "up", "down")


def reference_logits(weights, tokens, config: dict, precision: str = "f32"):
  """Logits ``[B, S, V]`` (float32) of the plain block over ``tokens``."""
  import jax
  import jax.numpy as jnp
  z = sizes(config)
  with jax.default_matmul_precision("highest"):
    w = {k: v.astype(jnp.float32) for k, v in weights.items()}
    x = jnp.take(w["embed"], tokens, axis=0)

    def body(x, layer):
      return _block(x, layer, z, precision), None

    x, _ = jax.lax.scan(body, x, {k: w[k] for k in _LAYER_LEAVES})
    x = _layer_norm(x, w["ln_f"])
    return _mm("bsd,vd->bsv", x, w["embed"], precision)


def reference_loss(weights, tokens, config: dict, precision: str = "f32"):
  """Mean next-token cross-entropy over ``tokens[:, 1:]``."""
  import jax
  import jax.numpy as jnp
  logits = reference_logits(weights, tokens, config, precision)[:, :-1]
  logp = jax.nn.log_softmax(logits, axis=-1)
  picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
  return -jnp.mean(picked)


def reference_train(weights, slab, config: dict, precision: str = "f32",
                    row_block: int = 4, drop_rows: int = 0):
  """Follow AdamW through ``slab`` ``[K, B, S]`` from ``weights``.

  Gradients are accumulated over blocks of ``row_block`` rows (a mean of
  equal blocks is the batch mean), so float32 at the timed batch fits beside
  nothing else.  Returns ``dict(losses=[K], mu=<leaf norms>,
  delta=<leaf norms>)``: the first-moment state after the K steps and the
  parameters' change, by leaf.  ``drop_rows`` leaves that many rows of each
  batch out (a fault for the tests to catch, never used in a run)."""
  import jax
  import jax.numpy as jnp
  import numpy as np
  hp = ADAMW
  grad_fn = jax.jit(jax.value_and_grad(
      lambda w, t: reference_loss(w, t, config, precision)))

  @jax.jit
  def adamw(w, g, m, v, t):
    m = jax.tree.map(lambda m, g: hp["b1"] * m + (1 - hp["b1"]) * g, m, g)
    v = jax.tree.map(lambda v, g: hp["b2"] * v + (1 - hp["b2"]) * g * g, v, g)
    c1, c2 = 1 - hp["b1"] ** t, 1 - hp["b2"] ** t

    def upd(p, m, v):
      step = (m / c1) / (jnp.sqrt(v / c2) + hp["eps"])
      return p - hp["lr"] * (step + hp["weight_decay"] * p)
    return jax.tree.map(upd, w, m, v), m, v

  w0 = {k: v.astype(jnp.float32) for k, v in weights.items()}
  w = w0
  m = jax.tree.map(jnp.zeros_like, w)
  v = jax.tree.map(jnp.zeros_like, w)
  losses = []
  slab = np.asarray(slab)
  for step in range(slab.shape[0]):
    rows = slab[step][:slab.shape[1] - drop_rows]
    if len(rows) % row_block:
      raise ValueError("row_block %d does not divide %d rows"
                       % (row_block, len(rows)))
    blocks = len(rows) // row_block
    loss, grads = 0.0, None
    for i in range(blocks):
      l, g = grad_fn(w, jnp.asarray(rows[i * row_block:(i + 1) * row_block]))
      loss = loss + l / blocks
      g = jax.tree.map(lambda x: x / blocks, g)
      grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    losses.append(float(loss))
    w, m, v = adamw(w, grads, m, v, jnp.float32(step + 1))
  delta = jax.tree.map(jnp.subtract, w, w0)
  return dict(losses=losses, mu=stacked_leaf_norms(m),
              delta=stacked_leaf_norms(delta))


# ---------------------------------------------------------------------------
# leaf names shared by both sides of a comparison
# ---------------------------------------------------------------------------


def stacked_leaf_norms(stacked) -> dict:
  """``{leaf name: l2 norm}`` of a stacked tree, one entry per layer leaf
  (names as ``program_leaf_norms`` gives them)."""
  import jax.numpy as jnp
  import numpy as np
  out = {}
  for name, arr in stacked.items():
    a = jnp.asarray(arr, jnp.float32)
    if name in _LAYER_LEAVES:
      norms = np.asarray(jnp.sqrt(jnp.sum(
          a.reshape(a.shape[0], -1) ** 2, axis=1)))
      for i, n in enumerate(norms):
        out["layer_%d/%s" % (i, name)] = float(n)
    else:
      out[name] = float(jnp.sqrt(jnp.sum(a ** 2)))
  return out


_PROGRAM_LEAF = {
    ("embed", "embedding"): "embed", ("ln_f", "scale"): "ln_f",
    ("ln1", "scale"): "ln1", ("ln2", "scale"): "ln2",
    ("attn", "q", "kernel"): "q", ("attn", "k", "kernel"): "k",
    ("attn", "v", "kernel"): "v", ("attn", "out", "kernel"): "out",
    ("mlp", "up", "kernel"): "up", ("mlp", "down", "kernel"): "down",
}


def _program_leaf_name(path) -> str:
  keys = tuple(str(getattr(p, "key", getattr(p, "name", p))) for p in path)
  if keys[0].startswith("layer_"):
    return "%s/%s" % (keys[0], _PROGRAM_LEAF[keys[1:]])
  return _PROGRAM_LEAF[keys]


def program_leaf_norms(tree) -> dict:
  """``{leaf name: l2 norm}`` of a tree in the PROGRAM's layout (params, a
  first-moment state or a difference of two), computed on the device and
  fetched as one vector."""
  import jax
  import jax.numpy as jnp
  import numpy as np
  flat = jax.tree_util.tree_flatten_with_path(tree)[0]
  norms = jax.jit(lambda leaves: jnp.stack(
      [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
       for x in leaves]))([x for _, x in flat])
  return {_program_leaf_name(p): float(n)
          for (p, _), n in zip(flat, np.asarray(norms))}


# ---------------------------------------------------------------------------
# the program half
# ---------------------------------------------------------------------------


def program_config(config: dict, max_seq_len: int, **overrides):
  """The program's ``TransformerConfig`` at this configuration's sizes."""
  from tensorflowonspark_tpu.models import transformer as tfm
  z = sizes(config)
  kw = dict(vocab_size=z["vocab"], num_layers=z["layers"],
            num_heads=z["heads"], d_model=z["d_model"], d_ff=z["d_ff"],
            max_seq_len=int(max_seq_len), remat=False)
  kw.update(overrides)
  return tfm.TransformerConfig(**kw)


def _to_program_tree(w, layers: int):
  tree = {"embed": {"embedding": w["embed"]}, "ln_f": {"scale": w["ln_f"]}}
  for i in range(layers):
    tree["layer_%d" % i] = {
        "ln1": {"scale": w["ln1"][i]}, "ln2": {"scale": w["ln2"][i]},
        "attn": {n: {"kernel": w[n][i]} for n in ("q", "k", "v", "out")},
        "mlp": {n: {"kernel": w[n][i]} for n in ("up", "down")}}
  return tree


def program_params(seed: int, config: dict, dtype="float32"):
  """The same weights as ``make_weights(seed, config, dtype)``, in the
  program's tree layout; one jitted call on the device."""
  import jax
  import jax.numpy as jnp
  z = sizes(config)
  key = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))
  fn = jax.jit(lambda k: _to_program_tree(
      _weights_impl(k, z, jnp.dtype(dtype)), z["layers"]))
  return fn(key)


def program_loss_fn(cfg):
  """``loss_fn(params, tokens)`` as a user of the program writes it."""
  from tensorflowonspark_tpu.models import transformer as tfm
  model = tfm.Transformer(cfg)

  def loss_fn(params, tokens):
    return tfm.causal_lm_loss(model.apply({"params": params}, tokens), tokens)
  return loss_fn


def program_train_state(params, cfg, seq_len: int):
  """The program's own TrainState (its default AdamW) around ``params``."""
  import jax
  from tensorflowonspark_tpu.models import transformer as tfm
  _, make_state = tfm._init_fns(jax.random.PRNGKey(0), cfg, None,
                                ADAMW["lr"], seq_len)
  return jax.jit(make_state)(params)


def first_moment(state):
  """Adam's first-moment tree out of the program's optimizer state."""
  import jax
  found = [s.mu for s in jax.tree.leaves(
      state.opt_state, is_leaf=lambda x: hasattr(x, "mu"))
           if hasattr(s, "mu")]
  if len(found) != 1:
    raise ValueError("expected one Adam state in opt_state, found %d"
                     % len(found))
  return found[0]
