"""Multi-head latent attention without rotary positions (the ``mla_use_nope``
form of Kimi Linear's global layers), as one mixer of
``models.transformer.Block`` (``layer_types[i] == "mla"``).

Per token the layer projects ONE latent ``[c_t ; r_t] = W_kva x_t`` (``c_t``
RMS-normalised, ``mla_kv_rank`` wide; ``r_t`` ``mla_rope_dim`` wide, shared
by the heads and, here, NOT rotated). Head ``h``'s key is ``[W_kb,h c_t ;
r_t]`` and its value ``W_vb,h c_t``. Decoding caches the latent and nothing
per head: ``cached_kv [b, max_seq, rank + rope_dim rounded up to whole
128-lane tiles]`` in the model's dtype (zeros in the padding: 576 -> 640; a
576-wide minor axis made the TPU's compiler keep the leaf TRANSPOSED in
memory and copy it whole, in and out, at every program's edge) beside the
``index`` cursor every position-indexed cache has, so the slab's cursor
logic (``serving/slots.py``) applies unchanged.

A narrow query block (a decode step) runs ABSORBED: ``W_kb`` goes into the
query and ``W_vb`` onto the output, so scores and values are two
contractions against the cache AS STORED (one shared "head" of ``rank +
rope_dim``: ``transformer._cache_contract``, no view, slice or copy of the
slab). A wide block (a prefill chunk on its one-row cache) expands keys and
values. Either way the cache is read as it was BEFORE the block's write and
the block's own entries join as a second part of the same softmax
(``transformer._cached_attention`` says why).
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from tensorflowonspark_tpu.models import transformer as tfm

_HI = lax.Precision.HIGHEST
_NEG = -1e30


def _two_part_softmax(s_cache, s_own, q_pos, seg):
  """Mask and exponentiate the scores of a query block against the cache
  ``[b, seg, h, max]`` (entries written before the block count) and against
  itself ``[b, seg, h, seg]`` (causal) under ONE maximum. Returns
  ``(e_cache, e_own, total [b, seg, h])``."""
  mx = s_cache.shape[-1]
  keep = jnp.arange(mx) < q_pos[:, :1, None]                  # [b|1, 1, max]
  own = jnp.arange(seg)
  causal = own[None, :] <= own[:, None]
  s_cache = jnp.where(keep[:, :, None, :], s_cache, _NEG)
  s_own = jnp.where(causal[None, :, None, :], s_own, _NEG)
  top = jnp.maximum(s_cache.max(axis=-1), s_own.max(axis=-1))[..., None]
  e_cache, e_own = jnp.exp(s_cache - top), jnp.exp(s_own - top)
  return e_cache, e_own, e_cache.sum(axis=-1) + e_own.sum(axis=-1)


class MLA(nn.Module):
  cfg: object
  mesh: object = None

  @nn.compact
  def __call__(self, x, decode: bool = False):
    cfg = self.cfg
    h, rank = cfg.num_heads, cfg.mla_kv_rank
    dn, dr, dv = cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim
    b, seg, _ = x.shape
    scale = (dn + dr) ** -0.5
    q = tfm.Proj(cfg, (h, dn + dr), name="q")(x)              # [b, seg, h, .]
    kva = tfm.Proj(cfg, (rank + dr,), name="kva")(x)
    c = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32,
                   name="kv_norm")(kva[..., :rank])
    # the latent as the cache stores it: what every path below attends
    width = -(-(rank + dr) // tfm._MXU_COLS) * tfm._MXU_COLS
    latent = jnp.concatenate(
        [c.astype(cfg.dtype), kva[..., rank:].astype(cfg.dtype),
         jnp.zeros((b, seg, width - rank - dr), cfg.dtype)], axis=-1)
    kvb = self.param("kvb", nn.initializers.lecun_normal(),
                     (rank, h, dn + dv), jnp.float32).astype(cfg.dtype)

    def expand(lat):           # [b, n, rank + dr] -> keys, values per head
      kv = tfm._weight_matmul("bnr,rhd->bnhd", lat[..., :rank], kvb, cfg)
      shared = jnp.broadcast_to(lat[:, :, None, rank:rank + dr],
                                lat.shape[:2] + (h, dr))
      return jnp.concatenate([kv[..., :dn], shared], axis=-1), kv[..., dn:]

    if not decode:
      k, v = expand(latent)
      s = tfm._act_einsum("bqhd,bkhd->bqhk", q, k, cfg) * scale
      own = jnp.arange(seg)
      s = jnp.where((own[None, :] <= own[:, None])[None, :, None, :], s, _NEG)
      return self._out(tfm._act_einsum(
          "bqhk,bkhd->bqhd", jax.nn.softmax(s, axis=-1),
          v.astype(jnp.float32), cfg))

    cached = self.variable("cache", "cached_kv", jnp.zeros,
                           (b, cfg.max_seq_len, width), cfg.dtype)
    cursor = self.variable("cache", "index",
                           lambda: jnp.zeros((), jnp.int32))
    idx = cursor.value
    if idx.ndim == 1:              # per-slot cursors (serving slab decode)
      positions = idx[:, None] + jnp.arange(seg)[None, :]
      q_pos = positions
    else:
      positions = idx + jnp.broadcast_to(jnp.arange(seg), (b, seg))
      q_pos = positions[:1]
    was = cached.value
    cached.value = tfm._cache_write(was, latent, idx, positions, self.mesh)
    cursor.value = idx + seg
    own_f = latent.astype(jnp.float32)

    if seg * h <= tfm._MXU_COLS:
      # absorbed: W_kb into the query, W_vb onto the output; the cache is
      # one shared head of rank + dr, contracted as stored
      q_abs = jnp.concatenate(
          [tfm._weight_matmul("bshd,rhd->bshr", q[..., :dn], kvb[..., :dn],
                              cfg, f32_out=True),
           q[..., dn:].astype(jnp.float32),
           jnp.zeros((b, seg, h, width - rank - dr), jnp.float32)],
          axis=-1)                                            # [b,seg,h,width]
      s_cache = tfm._cache_contract(
          "bnc,bkc->bnk", q_abs.reshape(b, seg * h, width),
          was).reshape(b, seg, h, -1) * scale
      s_own = jnp.einsum("bqhc,bkc->bqhk", q_abs, own_f,
                         precision=_HI) * scale
      e_cache, e_own, total = _two_part_softmax(s_cache, s_own, q_pos, seg)
      o_c = tfm._cache_contract(
          "bnk,bkc->bnc", e_cache.reshape(b, seg * h, -1),
          was)[..., :rank].reshape(b, seg, h, rank)
      o_c = o_c + jnp.einsum("bqhk,bkr->bqhr", e_own, own_f[..., :rank],
                             precision=_HI)
      return self._out(tfm._weight_matmul(
          "bshr,rhd->bshd", o_c / total[..., None], kvb[..., dn:], cfg,
          f32_out=True))

    # a wide block on its (one-row) cache: keys and values expanded
    k_own, v_own = expand(latent)
    k_was, v_was = expand(was)

    def scores(k):
      return tfm._act_einsum("bqhd,bkhd->bqhk", q, k, cfg) * scale

    e_cache, e_own, total = _two_part_softmax(scores(k_was), scores(k_own),
                                              q_pos, seg)
    o = tfm._act_einsum("bqhk,bkhd->bqhd", e_cache,
                        v_was.astype(jnp.float32), cfg) \
        + tfm._act_einsum("bqhk,bkhd->bqhd", e_own,
                          v_own.astype(jnp.float32), cfg)
    return self._out(o / total[..., None])

  def _out(self, o):
    return tfm.Proj(self.cfg, (self.cfg.d_model,), in_dims=2, name="out")(o)
