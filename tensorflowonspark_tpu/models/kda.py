"""Kimi Delta Attention (KDA): gated delta-rule linear attention with a
short causal convolution (Kimi Linear, arXiv:2510.26692), as one mixer of
``models.transformer.Block`` (``layer_types[i] == "kda"``).

Per head (``d_k = d_v = kda_head_dim``) the layer keeps a state ``S`` in
R^{d_k x d_v}, ``S_0 = 0``, and per token::

  S' = diag(a_t) S_{t-1};  u_t = v_t - S'^T k_t;  S_t = S' + b_t k_t u_t^T
  o_t = S_t^T q_t

with ``q, k`` l2-normalised (``q`` also scaled by ``d_k^-0.5``) after a
depthwise causal convolution and SiLU, a per-channel decay ``a_t`` in (0, 1)
and a per-head write strength ``b_t`` in (0, 1). What decoding keeps per
sequence is therefore NOT keys and values by position: the ``cache``
collection holds ``kda_state [b, H, d_k, d_v]`` (float32: the state is a
running sum, kept at the precision it is accumulated in) and ``conv_tail
[b, taps - 1, 3 H d_k]`` (the last pre-convolution projections, in the
dtype the projections come out in: the compute dtype, or float32 under
``cfg.act_f32``). Neither has a position axis, so nothing
that pages, shares or rolls a cache back by position applies to this layer
(``serving/slots.py`` refuses those by name).

Two forms of the same recurrence, leaving the same state:

* one token (a decode step, a one-token chunk): the recurrence itself, two
  passes over ``S`` (both read-outs share one, the update is the other),
  elementwise in float32;
* a chunk (prefill): the chunkwise form in blocks of ``BLOCK`` tokens. With
  ``G_t`` the running sum of ``log a`` inside a block the recurrence
  unrolls to a unit lower-triangular system ``(I + tril(A, -1) diag(b)) U =
  V - (K * exp G) S_0`` with ``A[t, i] = sum_c k_t[c] k_i[c] exp(G_t[c] -
  G_i[c])``; it is inverted once a block, the state is carried from block to
  block (a ``lax.scan`` of at most chunk / BLOCK steps, never one of chunk
  steps) and from call to call. ``exp(G_t - G_i)`` is taken of the
  DIFFERENCE, masked to ``t >= i`` first, so no factor overflows however
  fast a channel decays.

A chunk may end in PADDING (``n_valid`` real tokens of ``seg``: a prompt's
tail padded up to a prefill bucket, ``serving/slots.py``). A padded token
neither decays nor writes (``log a = 0, b = 0``: ``S' = S`` and ``b k u^T =
0``, the same tokens ``chunk_rule`` fills a last block up with), so the
state after the chunk is the state after its last real token, and the
convolution tail is taken at the true length, ``window[n_valid : n_valid +
taps - 1]`` of the old tail followed by the chunk. What the layer returns at
a padded position is of no use to anyone, and nothing reads it.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from tensorflowonspark_tpu.models import transformer as tfm

#: tokens a block of the chunkwise form solves together
BLOCK = 64
_HI = lax.Precision.HIGHEST
_L2_EPS = 1e-6


def _l2norm(x):
  return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS)


def recurrent_step(state, q, k, v, a, beta):
  """One token of the recurrence. ``state [b, H, dk, dv]``; ``q, k, a
  [b, H, dk]``; ``v [b, H, dv]``; ``beta [b, H]``; all float32. Returns
  ``(new_state, o [b, H, dv])``. ``S'^T k`` and ``S'^T q`` are read in ONE
  pass over the state (``o = S'^T q + (k.q) b u``), the update is the
  second: the state is all a decode step of this layer moves."""
  kq = jnp.stack([a * k, a * q], axis=2)                     # [b, H, 2, dk]
  read = jnp.sum(state[:, :, None] * kq[..., None], axis=3)  # [b, H, 2, dv]
  bu = beta[..., None] * (v - read[:, :, 0])
  new = a[..., None] * state + k[..., None] * bu[:, :, None, :]
  o = read[:, :, 1] + jnp.sum(k * q, axis=-1, keepdims=True) * bu
  return new, o


def _unit_lower_inverse(m):
  """Inverse of unit lower-triangular ``m [..., n, n]`` (n a power of two)
  by halves: ``[[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]]``,
  from 1 x 1 blocks up. log2(n) rounds of two small matmuls, no loop over
  rows, and none of the cancellation a Neumann series of ``m - I`` has."""
  n = m.shape[-1]
  lead = m.shape[:-2]
  inv = jnp.ones(lead + (n, 1, 1), m.dtype)
  s = 1
  while s < n:
    g = n // (2 * s)
    blocks = m.reshape(lead + (g, 2 * s, g, 2 * s))
    diag = jnp.moveaxis(jnp.diagonal(blocks, axis1=-4, axis2=-2), -1, -3)
    pair = inv.reshape(lead + (g, 2, s, s))
    a_inv, b_inv = pair[..., 0, :, :], pair[..., 1, :, :]
    c = diag[..., s:, :s]
    low = -jnp.einsum("...ij,...jk,...kl->...il", b_inv, c, a_inv,
                      precision=_HI)
    inv = jnp.concatenate(
        [jnp.concatenate([a_inv, jnp.zeros_like(a_inv)], axis=-1),
         jnp.concatenate([low, b_inv], axis=-1)], axis=-2)
    s *= 2
  return inv[..., 0, :, :]


def _block_step(state, blk):
  """One block of the chunkwise form. ``state [b, H, dk, dv]``; ``blk`` =
  ``(q, k [b, H, C, dk], v [b, H, C, dv], la [b, H, C, dk], beta
  [b, H, C])`` with ``la = log a <= 0``. Returns ``(new_state, o
  [b, H, C, dv])``."""
  q, k, v, la, beta = blk
  c = q.shape[2]
  g = jnp.cumsum(la, axis=2)                                  # [b, H, C, dk]
  # the channel axis leads inside the [.., dk, C, C] products, so that
  # summing it adds whole tiles instead of reducing across lanes
  gt, kt, qt = (jnp.swapaxes(x, 2, 3) for x in (g, k, q))     # [b, H, dk, C]
  keep = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]     # t >= i
  decay = jnp.exp(jnp.where(keep, gt[..., :, None] - gt[..., None, :],
                            -jnp.inf))                        # [b,H,dk,C,C]
  kd = kt[..., None, :] * decay                               # k_i D[t, i]
  a_mat = jnp.sum(kt[..., :, None] * kd, axis=2)              # [b, H, C, C]
  p_mat = jnp.sum(qt[..., :, None] * kd, axis=2)              # t >= i only
  low = jnp.tril(a_mat, -1) * beta[..., None, :]
  t_inv = _unit_lower_inverse(jnp.eye(c, dtype=low.dtype) + low)
  eg = jnp.exp(g)
  rhs = v - jnp.einsum("bhck,bhkv->bhcv", k * eg, state, precision=_HI)
  bu = beta[..., None] * jnp.einsum("bhti,bhiv->bhtv", t_inv, rhs,
                                    precision=_HI)
  o = jnp.einsum("bhck,bhkv->bhcv", q * eg, state, precision=_HI) \
      + jnp.einsum("bhti,bhiv->bhtv", p_mat, bu, precision=_HI)
  g_end = g[:, :, -1]                                         # [b, H, dk]
  new = jnp.exp(g_end)[..., None] * state + jnp.einsum(
      "bhck,bhcv->bhkv", k * jnp.exp(g_end[:, :, None, :] - g), bu,
      precision=_HI)
  return new, o


def chunk_rule(state, q, k, v, la, beta):
  """The recurrence over a chunk. ``state [b, H, dk, dv]``; ``q, k, la
  [b, seg, H, dk]``; ``v [b, seg, H, dv]``; ``beta [b, seg, H]``; float32.
  Returns ``(new_state, o [b, seg, H, dv])``. The chunk is padded to whole
  blocks with tokens that neither decay nor write (``log a = 0, b = 0``)."""
  seg = q.shape[1]
  c = min(BLOCK, 1 << (seg - 1).bit_length())
  n = -(-seg // c)

  def blocks(x):             # [b, seg, H, ...] -> [n, b, H, c, ...]
    x = jnp.pad(x, [(0, 0), (0, n * c - seg)] + [(0, 0)] * (x.ndim - 2))
    x = x.reshape((x.shape[0], n, c) + x.shape[2:])
    return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)

  xs = tuple(blocks(x) for x in (q, k, v, la)) \
      + (blocks(beta[..., None])[..., 0],)
  if n == 1:
    state, o = _block_step(state, tuple(x[0] for x in xs))
    o = o[None]
  else:
    state, o = lax.scan(_block_step, state, xs)
  # [n, b, H, c, dv] -> [b, seg, H, dv]
  o = jnp.moveaxis(jnp.moveaxis(o, 3, 2), 0, 1)
  return state, o.reshape((o.shape[0], n * c) + o.shape[3:])[:, :seg]


class KDA(nn.Module):
  """The KDA mixer: ``x [b, seg, d_model]`` (normalised) -> ``[b, seg,
  d_model]``. ``decode=True`` carries ``kda_state`` and ``conv_tail`` in
  the ``cache`` collection: a call resumes from what they hold, whatever
  the cursor of the model's other layers says, and leaves them as the
  per-token recurrence over its tokens would: over the first ``n_valid``
  of them (a traced int32 scalar, at least 1) where that is given, the
  rest being padding; ``None`` = every token is real."""
  cfg: object

  @nn.compact
  def __call__(self, x, decode: bool = False, n_valid=None):
    cfg = self.cfg
    h, dk, taps, rank = (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv,
                         cfg.kda_rank)
    width = h * dk
    b, seg, _ = x.shape
    if seg == 1:
      n_valid = None             # one token is real: the decode step's form

    def dense(feats, name):
      return tfm.Proj(cfg, (feats,), name=name)

    qkv = jnp.concatenate([dense(width, n)(x) for n in ("q", "k", "v")],
                          axis=-1)                            # [b, seg, 3W]
    conv = self.param("conv", nn.initializers.normal(taps ** -0.5),
                      (taps, 3 * width), jnp.float32)
    a_log = self.param("A_log", nn.initializers.zeros, (h,), jnp.float32)
    dt_bias = self.param("dt_bias", nn.initializers.zeros, (width,),
                         jnp.float32)
    o_scale = self.param("o_norm", nn.initializers.ones, (dk,), jnp.float32)
    f32 = jnp.float32
    decay_in = dense(width, "f2")(dense(rank, "f1")(x)).astype(f32)
    beta = jax.nn.sigmoid(dense(h, "b")(x).astype(f32))       # [b, seg, H]
    gate = jax.nn.sigmoid(dense(width, "g2")(dense(rank, "g1")(x))
                          .astype(f32)).reshape(b, seg, h, dk)

    if decode:
      state = self.variable("cache", "kda_state", jnp.zeros,
                            (b, h, dk, dk), f32)
      tail = self.variable("cache", "conv_tail", jnp.zeros,
                           (b, taps - 1, 3 * width), qkv.dtype)
      window = jnp.concatenate([tail.value, qkv], axis=1)
      # the old tail leads the window, so a chunk with fewer real tokens
      # than the tail is long keeps the old tail's last rows before them
      tail.value = window[:, seg:] if n_valid is None else \
          lax.dynamic_slice_in_dim(window, n_valid, taps - 1, axis=1)
      s0 = state.value
    else:
      window = jnp.pad(qkv, [(0, 0), (taps - 1, 0), (0, 0)])
      s0 = jnp.zeros((b, h, dk, dk), f32)
    # depthwise causal convolution: tap taps-1 meets the token itself
    window = window.astype(f32)
    mixed = sum(conv[j] * window[:, j:j + seg] for j in range(taps))
    q, k, v = (t.reshape(b, seg, h, dk)
               for t in jnp.split(jax.nn.silu(mixed), 3, axis=-1))
    q = _l2norm(q) * dk ** -0.5
    k = _l2norm(k)
    # log a = -exp(A_log_h) softplus(f(x) + dt_bias), one value a channel
    la = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
        (decay_in + dt_bias).reshape(b, seg, h, dk))
    if n_valid is not None:
      real = jnp.arange(seg) < n_valid
      la = jnp.where(real[:, None, None], la, 0.0)
      beta = jnp.where(real[:, None], beta, 0.0)

    if decode and seg == 1:
      new, o = recurrent_step(s0, q[:, 0], k[:, 0], v[:, 0],
                              jnp.exp(la[:, 0]), beta[:, 0])
      o = o[:, None]
    else:
      new, o = chunk_rule(s0, q, k, v, la, beta)
    if decode:
      state.value = new
    # per-head RMSNorm over d_v, gated
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps)
    return dense(cfg.d_model, "out")(
        (o * o_scale * gate).reshape(b, seg, width))
