"""Multi-head latent attention as one mixer of ``models.transformer.Block``
(``layer_types[i] == "mla"``): the ``mla_use_nope`` form of Kimi Linear's
global layers (one full query map, nothing rotated: the defaults) and the
DeepSeek form (``TransformerConfig.mla_q_rank`` / ``mla_rope`` /
``rope_yarn_*``: the query through a rank, the queries' last ``mla_rope_dim``
dims and the shared key part rotated at the token's position, at YaRN's
frequencies, the softmax scale times YaRN's ``m^2``).

Per token the layer projects ONE latent ``[c_t ; r_t] = W_kva x_t`` (``c_t``
RMS-normalised, ``mla_kv_rank`` wide; ``r_t`` ``mla_rope_dim`` wide, shared
by the heads, rotated under ``mla_rope``). Head ``h``'s key is ``[W_kb,h c_t ;
r_t]`` and its value ``W_vb,h c_t``. Decoding caches the latent and nothing
per head: ``cached_kv [b, max_seq, rank + rope_dim rounded up to whole
128-lane tiles]`` in the model's dtype (zeros in the padding: 576 -> 640; a
576-wide minor axis made the TPU's compiler keep the leaf TRANSPOSED in
memory and copy it whole, in and out, at every program's edge), the key part
AS ROTATED, beside the ``index`` cursor every position-indexed cache has, so
the slab's cursor logic (``serving/slots.py``) applies unchanged.

A narrow query block runs ABSORBED: ``W_kb`` goes into the query and ``W_vb``
onto the output, so scores and values are two contractions against the cache
AS STORED (one shared "head" of ``rank + rope_dim``). ``seg * heads <= 128``
picks that branch: with 32 heads a block of up to 4 tokens, with 128 heads ONE
token, which is the decode step. The absorbed read has two lowerings, chosen
from what the code can observe (as ``transformer._cached_attention``
chooses): under per-slot cursors, one token a slot, bf16 activations and a
bf16 leaf of whole blocks on one device it is ``ops.decode_attention`` handed
the leaf as K and as V: the kernel stops at each slot's cursor (the absorbed
query ONE bf16 term, the probabilities three exact ones; a block of latent
rows comes twice, as keys and as values: bringing it once was worth 3.5% of
the kernel alone on the chip, PERF.md section 6); everything else (float32
activations, a shared cursor, the CPU) contracts against the whole leaf and
masks (``transformer._cache_contract``, the query's three bf16 terms).

A wide block (a prefill chunk on its one-row cache) expands keys and values
per head. With bf16 activations where the flash kernels are in play it goes
through the flash FORWARD at the heads' two widths (keys ``nope + rope``,
values ``v``): a chunk at cursor 0 over itself, a later chunk of a long row
over the row AS WRITTEN in blocks of ``transformer._ROW_BLOCK`` rows, the
latent expanded a block at a time and the partials merged, so no ``[seg,
heads, max]`` score tensor and no expanded row exist. Float32 activations
(what the flash kernels would round) keep the dense wide branch: the cache is
read as it was BEFORE the block's write and the block's own entries join as
a second part of the same softmax (``transformer._cached_attention`` says
why).
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from tensorflowonspark_tpu import ops
from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.ops.flash_attention import NEG_INF

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
    single = self.mesh is None or self.mesh.size == 1
    if not single and (cfg.mla_rope or cfg.mla_q_rank):
      raise ValueError(
          "a mesh of %d devices cannot take a latent layer with a query rank "
          "or a rotated key part (mla_q_rank=%d, mla_rope=%r): its kernels "
          "(the decode read of the one leaf, the flash forward of a chunk) "
          "are not mapped over shards, and a sharding of the rank or of the "
          "128 heads is not built" % (self.mesh.size, cfg.mla_q_rank,
                                      cfg.mla_rope))
    scale = (dn + dr) ** -0.5
    if cfg.rope_yarn_factor:
      scale = scale * tfm.yarn_softmax_factor(cfg)
    if cfg.mla_q_rank:
      cq = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32, name="q_norm")(
          tfm.Proj(cfg, (cfg.mla_q_rank,), name="q_a")(x))
      q = tfm.Proj(cfg, (h, dn + dr), name="q_b")(cq)         # [b, seg, h, .]
    else:
      q = tfm.Proj(cfg, (h, dn + dr), name="q")(x)
    kva = tfm.Proj(cfg, (rank + dr,), name="kva")(x)
    c = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32,
                   name="kv_norm")(kva[..., :rank])
    width = -(-(rank + dr) // tfm._MXU_COLS) * tfm._MXU_COLS

    freqs = tfm.yarn_frequencies(
        cfg.rope_theta, dr, cfg.rope_yarn_factor, cfg.rope_yarn_original,
        cfg.rope_yarn_beta_fast, cfg.rope_yarn_beta_slow) \
        if cfg.rope_yarn_factor else None

    def rotated(t, positions):      # the last dr dims of [b, seg, n, .]
      if not cfg.mla_rope:
        return t
      return jnp.concatenate(
          [t[..., :-dr], tfm._rotary(t[..., -dr:], positions, cfg.rope_theta,
                                     freqs=freqs)], axis=-1)

    def latent_at(positions):
      # the latent as the cache stores it: what every path below attends
      def shared():
        if not cfg.mla_rope:
          return kva[..., rank:]
        return rotated(kva[:, :, None, rank:], positions)[:, :, 0]

      return jnp.concatenate(
          [c.astype(cfg.dtype), shared().astype(cfg.dtype),
           jnp.zeros((b, seg, width - rank - dr), cfg.dtype)], axis=-1)

    # an unrotated latent waits for no position (and is traced here, where it
    # always was)
    latent = None if cfg.mla_rope else latent_at(None)
    kvb = self.param("kvb", nn.initializers.lecun_normal(),
                     (rank, h, dn + dv), jnp.float32).astype(cfg.dtype)

    def expand(lat):           # [b, n, rank + dr] -> keys, values per head
      kv = tfm._weight_matmul("bnr,rhd->bnhd", lat[..., :rank], kvb, cfg)
      shared = jnp.broadcast_to(lat[:, :, None, rank:rank + dr],
                                lat.shape[:2] + (h, dr))
      return jnp.concatenate([kv[..., :dn], shared], axis=-1), kv[..., dn:]

    if not decode:
      positions = jnp.arange(seg)[None, :]
      q = rotated(q, positions)
      k, v = expand(latent_at(positions) if latent is None else latent)
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
    vec = idx.ndim == 1            # per-slot cursors (serving slab decode)
    if vec:
      positions = idx[:, None] + jnp.arange(seg)[None, :]
      q_pos = positions
    else:
      positions = idx + jnp.broadcast_to(jnp.arange(seg), (b, seg))
      q_pos = positions[:1]
    q = rotated(q, positions)
    if latent is None:
      latent = latent_at(positions)
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
      if vec and seg == 1:
        ragged = (not cfg.act_f32 and single
                  and ops.decode_attention_supports(
                      (b, h, width), cfg.dtype, was.shape, was.dtype)
                  and ops.pallas_kernels_enabled())
        tally = getattr(tfm._attn_reads, "open", None)
        if tally is not None:
          tally["reads"] += 1
          tally["ragged"] += ragged
        if ragged:
          # the leaf is K and V at once: its row's first lanes are values
          own = latent[:, 0, None]
          o_c = ops.decode_attention(
              q_abs[:, 0].astype(cfg.dtype), own, own, was, was, idx,
              scale=scale,
              interpret=ops.pallas_interpret())[:, None, :, :rank]
          return self._out(tfm._weight_matmul(
              "bshr,rhd->bshd", o_c, kvb[..., dn:], cfg, f32_out=True))
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

    k_own, v_own = expand(latent)
    if not vec and single and not cfg.act_f32 \
        and cfg.attention_impl != "dense" and tfm._flash_tiles(seg) \
        and tfm._flash_eligible(cfg, seg):
      # a prefill chunk through the flash forward at keys of dn + dr and
      # values of dv; the cursor is traced, so one program serves a chunk at
      # 0 (itself alone) and a later one (its row in blocks)
      interp = ops.pallas_interpret()
      rows = tfm._ROW_BLOCK

      def fresh(_):
        return ops.flash_attention_block(
            q, k_own, v_own, 0, 0, causal=True, interpret=interp,
            scale=scale)[0].astype(jnp.float32)

      def blocked(_):
        # the row AS WRITTEN above holds this chunk too, so one causal mask
        # over absolute positions covers cache and chunk; a block past the
        # chunk is never touched, and a block is expanded as it is met
        def one_block(j, partial):
          base = j * rows
          kj, vj = expand(lax.dynamic_slice_in_dim(
              cached.value, base, rows, axis=1))
          return ops.merge_partials(*partial, *ops.flash_attention_block(
              q, kj, vj, idx, base, causal=True, interpret=interp,
              scale=scale))

        return lax.fori_loop(
            0, (idx + seg - 1) // rows + 1, one_block,
            (jnp.zeros((b, seg, h, dv), q.dtype),
             jnp.full((b, h, seg), NEG_INF, jnp.float32)))[0].astype(
                 jnp.float32)

      def dense(_):
        return self._wide(q, k_own, v_own, expand(was), q_pos, scale)

      long_row = cfg.max_seq_len > rows and cfg.max_seq_len % rows == 0
      return self._out(lax.cond(idx == 0, fresh,
                                blocked if long_row else dense, None))
    return self._out(self._wide(q, k_own, v_own, expand(was), q_pos, scale))

  def _wide(self, q, k_own, v_own, was, q_pos, scale):
    """A wide block on its (one-row) cache, keys and values expanded (``was``
    the cache's, as it was before the block): the dense two-part softmax."""
    cfg = self.cfg
    k_was, v_was = was

    def scores(k):
      return tfm._act_einsum("bqhd,bkhd->bqhk", q, k, cfg) * scale

    e_cache, e_own, total = _two_part_softmax(
        scores(k_was), scores(k_own), q_pos, q.shape[1])
    o = tfm._act_einsum("bqhk,bkhd->bqhd", e_cache,
                        v_was.astype(jnp.float32), cfg) \
        + tfm._act_einsum("bqhk,bkhd->bqhd", e_own,
                          v_own.astype(jnp.float32), cfg)
    return o / total[..., None]

  def _out(self, o):
    return tfm.Proj(self.cfg, (self.cfg.d_model,), in_dims=2, name="out")(o)
