"""Sparse experts as ONE CHIP'S SHARE of an expert-parallel layer, as one
feed-forward of ``models.transformer.Block`` (``ffn_types[i] ==
"experts"``): sigmoid router over the published ``experts_total`` (or,
``experts_score="softmax"``, a softmax one with no selection bias), top
``experts_top_k`` a token (inside its ``experts_groups_kept`` best of
``experts_groups`` groups, where the router has that limit) with renormalised
weights times
``experts_scale``, gated SiLU experts of ``experts_d_ff``, plus
``experts_shared`` shared experts every token passes (dense).

The program holds experts ``[experts_first, experts_first + experts_held)``
only (``parallel.expert_parallel.held_experts_ffn``). What the experts held
on other chips would have added is left out: on one chip the layer runs
without its exchange, and nothing here stands in for it.

Counters: under ``mutable=["counters"]`` each call sows, per token, how many
of its assignments went to a held expert (``held [T]``) and which held
experts it chose (``hit [T, held]``) and, where the router has a group limit
(``TransformerConfig.experts_groups``), whether the groups it kept include one
that a held expert lies in (``group [T]``: a token that kept none of them can
have no assignment here); ``serving.slots`` sums them over live lanes.
Without the collection the sow is a no-op.
"""

import flax.linen as nn
import jax.numpy as jnp

from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.parallel import expert_parallel as ep


class _Shared(nn.Module):
  cfg: object

  @nn.compact
  def __call__(self, x):
    cfg = self.cfg
    return tfm._swiglu(x, cfg.experts_shared * cfg.experts_d_ff, cfg)


class HeldExperts(nn.Module):
  cfg: object
  mesh: object = None

  @nn.compact
  def __call__(self, x):
    cfg = self.cfg
    d, f, held = cfg.d_model, cfg.experts_d_ff, cfg.experts_held
    lecun = nn.initializers.lecun_normal()
    router = self.param("router", lecun, (d, cfg.experts_total), jnp.float32)
    soft = cfg.experts_score == "softmax"      # no selection bias exists
    bias = None if soft else self.param(
        "router_bias", nn.initializers.zeros, (cfg.experts_total,),
        jnp.float32)
    # fan-in is the middle axis of a [held, in, out] stack
    stack = nn.initializers.variance_scaling(1.0, "fan_in", "normal",
                                             in_axis=1, out_axis=2,
                                             batch_axis=0)
    gate, up, down = (
        self.param(name, stack, shape, jnp.float32).astype(cfg.dtype)
        for name, shape in (("gate", (held, d, f)), ("up", (held, d, f)),
                            ("down", (held, f, d))))
    flat = x.reshape(-1, d)       # the router sees it unrounded
    experts, weights, *kept = ep.route_softmax_topk(
        flat, router, cfg.experts_top_k, cfg.experts_scale) if soft \
        else ep.route_sigmoid_topk(
            flat, router, bias, cfg.experts_top_k, cfg.experts_scale,
            cfg.experts_groups, cfg.experts_groups_kept)
    split = None
    if cfg.act_f32 and gate.dtype == jnp.bfloat16:
      split = tfm._bf16_terms
    y, hit = ep.held_experts_ffn(
        flat, experts, weights, gate, up, down, cfg.experts_first, split,
        self.mesh, getattr(tfm._expert_products, "open", None))
    self.sow("counters", "held", jnp.sum(hit, axis=1, dtype=jnp.int32))
    local = jnp.where(hit, experts - cfg.experts_first, held)
    self.sow("counters", "hit",
             jnp.any(local[..., None] == jnp.arange(held), axis=1))
    if kept:
      # whether the token kept a group that some held expert lies in
      per = cfg.experts_total // cfg.experts_groups
      lo, hi = cfg.experts_first // per, \
          (cfg.experts_first + held - 1) // per + 1
      self.sow("counters", "group", jnp.any(kept[0][:, lo:hi], axis=1))
    y = y.reshape(x.shape)
    if not cfg.act_f32:
      y = y.astype(cfg.dtype)
    if cfg.experts_shared:
      y = y + _Shared(cfg, name="shared")(x)
    return y
