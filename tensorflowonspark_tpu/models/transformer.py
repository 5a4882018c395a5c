"""Decoder-only Transformer: the long-context flagship model family.

The reference had no attention model at all (SURVEY.md §5); this family is
the showcase for the framework's TPU-native parallelism: tensor parallelism
(megatron-style column/row sharding via flax logical axes), FSDP parameter
sharding, and sequence parallelism through ring attention
(parallel/ring_attention.py). bfloat16 compute / float32 params+softmax,
rotary position embeddings, remat-friendly block structure.

Logical axis names map to mesh axes through
``parallel.sharding.LOGICAL_RULES``:
  vocab/heads/mlp -> tensor axis, embed -> fsdp axis,
  batch -> data+fsdp, sequence -> sequence axis.
"""

import contextlib
import dataclasses
import functools
import math
import threading
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
import flax.linen as nn

from tensorflowonspark_tpu import ops
from tensorflowonspark_tpu.obs import device as obs_device
from tensorflowonspark_tpu.parallel import mesh as mesh_lib
from tensorflowonspark_tpu.parallel import ring_attention as ra


@dataclass(frozen=True)
class TransformerConfig:
  vocab_size: int = 32000
  num_layers: int = 12
  num_heads: int = 12
  d_model: int = 768
  d_ff: int = 3072
  max_seq_len: int = 2048
  dtype: Any = jnp.bfloat16
  remat: bool = True
  # What remat SAVES at block boundaries (active only when remat=True):
  # "none" recomputes everything in the backward (max memory savings,
  # ~21% step-time cost measured at the bench shape); "dots" saves MXU
  # (matmul) outputs and recomputes only cheap elementwise/VPU work — a
  # fraction of the recompute cost for most of the memory win, usually
  # the better batch-size lever on TPU (HBM-bound regime)
  remat_policy: str = "none"
  use_ring_attention: bool = False   # set True when seq is mesh-sharded
  # "auto": Pallas flash attention on TPU, dense elsewhere; "flash" forces
  # the kernel everywhere (interpret mode off-TPU — how CPU CI exercises
  # the production attention path); "dense" opts out
  attention_impl: str = "auto"
  # Grouped-query attention: 0 means = num_heads (vanilla MHA); 1 is MQA.
  # K/V are projected to this many heads and the per-layer KV cache stores
  # only them — a num_heads/num_kv_heads reduction in serving cache memory
  num_kv_heads: int = 0
  # Sliding-window attention (Mistral convention: each position attends
  # to its last `attention_window` positions, itself included; 0 = full
  # causal). The flash kernels bound their block loops to the window, so
  # attention FLOPs become O(seq·window); composed with ring attention,
  # ring steps whose KV shard slid out of the window collapse to zero
  # kernel-loop iterations. Training, prefill and KV-cache decode all
  # honor it.
  attention_window: int = 0
  # "auto": fused Pallas LayerNorm (ops.layer_norm) on TPU, flax elsewhere;
  # "fused" forces the kernel everywhere (interpret mode off-TPU — how CPU
  # CI exercises the production code path); "flax" opts out
  layer_norm_impl: str = "auto"
  # Mixture-of-experts: when moe_experts > 0, every `moe_every`-th layer
  # (moe_every >= 1) replaces its dense MLP with an expert-routed FFN
  # (parallel.expert_parallel; experts shard over the `expert` mesh axis)
  moe_experts: int = 0
  moe_top_k: int = 1
  moe_every: int = 2
  # > 0 enables GShard-style all-to-all dispatch with this capacity factor
  # when the expert mesh axis is sharded (communication-optimal; overflow
  # tokens above ceil(T_local·k/E)·factor are dropped); 0 keeps the exact
  # dense-masked dispatch
  moe_capacity_factor: float = 0.0
  # "model": the KV cache stores cfg.dtype; "int8": per-token/head
  # symmetric int8 with f32 scales — decode is HBM-bound on re-reading
  # the cache every step, so halving its bytes (vs bf16) is a direct
  # decode-throughput lever at ~0.4% per-entry quantization error. The
  # flash prefill is unaffected (it attends the raw projections); the
  # dense paths apply the scales to k-indexed tensors (scores/probs), so
  # no dequantized cache-sized copy exists in the program — asserted on
  # compiled TPU HLO (tests/test_mosaic_gate.py).
  kv_cache_dtype: str = "model"
  # Paged KV decode cache (the serving plane's HBM-capacity lever,
  # serving/slots.py): kv_page_size > 0 replaces each layer's contiguous
  # [batch, max_seq_len, ...] decode cache with a shared page POOL
  # ([kv_num_pages, kv_page_size, kv_heads, head_dim]) plus a per-slot
  # page table ([batch, kv_pages_per_slot] int32) and a VECTOR cursor.
  # A slot then holds only the pages its token mass needs, so slot count
  # scales with actual tokens instead of num_slots × max_seq_len worst
  # case. Page 0 is the TRASH page: never allocated, the sink for
  # frozen-lane writes and unused table entries. Training/prefill paths
  # are untouched (paging applies to decode=True with vector cursors).
  kv_page_size: int = 0
  kv_num_pages: int = 0
  kv_pages_per_slot: int = 0
  # "gather": table lookup with the embed dim explicitly replicated first,
  # so SPMD slices the gather result instead of involuntarily rematerializing
  # the [B, S, D] activation (the round-2 dryrun warning); "one_hot": contract
  # a one-hot over the vocab-sharded table — no table all-gather at all, at
  # 2·B·S·V·D extra FLOPs, the right trade for huge vocabs on large meshes
  embed_lookup: str = "gather"
  # Per-layer block spec (ROADMAP R1). ``layer_types[i]`` names layer i's
  # token mixer: "attn" (the attention above), "kda" (gated delta-rule
  # linear attention, models/kda.py) or "mla" (latent attention,
  # models/mla.py: ``mla_*`` below); ``ffn_types[i]`` its feed-forward:
  # "mlp" or "experts" (held sparse experts, models/experts.py). () keeps
  # every layer "attn" and the moe_experts/moe_every rule: GPT-2 is that
  # one spec. The new layers' modules are imported only when asked for.
  layer_types: tuple = ()
  ffn_types: tuple = ()
  norm: str = "layer"          # "layer" (LayerNorm) | "rms" (RMSNorm)
  norm_eps: float = 1e-6
  mlp_act: str = "gelu"        # "gelu" (ungated) | "swiglu" (gate/up/down)
  tie_embeddings: bool = True  # False: an untied ``head`` projection
  attn_head_dim: int = 0       # 0 = d_model // num_heads
  # KDA: heads of kda_head_dim keys AND values, a depthwise causal
  # convolution of kda_conv taps, low-rank decay/gate maps of kda_rank
  kda_heads: int = 0
  kda_head_dim: int = 128
  kda_conv: int = 4
  kda_rank: int = 128
  # MLA: num_heads query heads of (nope + rope) dims, one shared latent of
  # mla_kv_rank (+ mla_rope_dim unrotated key dims) a token
  mla_kv_rank: int = 512
  mla_nope_dim: int = 128
  mla_rope_dim: int = 64
  mla_v_dim: int = 128
  # Held experts: the router is experts_total wide (the published count);
  # THIS program holds experts [experts_first, experts_first + experts_held)
  # and computes their part of the layer for the tokens routed to them
  experts_total: int = 0
  experts_held: int = 0
  experts_first: int = 0
  experts_top_k: int = 8
  experts_d_ff: int = 0
  experts_shared: int = 1      # shared experts (dense, every token)
  experts_scale: float = 1.0   # routed_scaling_factor
  # The typed layers' ACTIVATION precision at a matrix product with bf16
  # weights (``Proj``, ``_weight_matmul``). False: the activation is rounded
  # to the compute dtype (one MXU pass). True: the float32 activation goes
  # in as its three bf16 terms stacked along the rows, so it meets the bf16
  # weights EXACTLY (three passes over the same weight bytes: free where a
  # step is bound by bytes, 3x the MXU work in a prefill chunk), and the
  # residual stream stays float32. A top-k router makes a sparse model with
  # random weights chaotic: an activation rounded to bf16 moves a near-tie
  # at the k-th place, the token passes another expert, and the stream is
  # 12% off the float32 reference by layer 27 (PERF.md section 6, PR 26);
  # with float32 activations the program follows the reference. It is what
  # the benchmark's check asks of this model, not what a deployment runs:
  # trained weights route with margins, and the check decides on the widest
  # gap of a served token (PERF.md section 7 asks for a p99 or mean limit).
  act_f32: bool = False
  # Rotary base (``_rotary``'s theta): positions rotate at theta^(-2i/d).
  rope_theta: float = 10000.0
  # Sandwich norms: a norm AFTER each branch too, before it joins the
  # residual stream (``x += norm(Mix(norm(x))); x += norm(FFN(norm(x)))``:
  # params ``ln1_out``/``ln2_out`` beside ``ln1``/``ln2``). False: pre-norm.
  post_norm: bool = False
  # A looped model (ROADMAP R10): the ``num_layers`` layers run
  # ``loop_passes`` times a token over the SAME weights, the final norm at
  # the end of every pass (its output enters the next pass). 1 = every layer
  # once, the final norm once: the programs traced before the field existed.
  # The parameter tree holds ONE set of layers whatever the count; the decode
  # cache holds keys and values PER PASS (pass u of layer l attends that
  # pass's keys only, so a full forward fixes it: ``cached_k_p<u>`` /
  # ``cached_v_p<u>``, each the shape a layer's leaf always had) and ONE
  # cursor a layer, advanced once a token. After each pass but the last an
  # exit gate ``sigmoid(w . x + b)`` (param ``exit_gate``) reads the normed
  # stream; a token EXITS at the first pass whose cumulative exit
  # probability reaches ``loop_exit_threshold`` (the last pass at 1.0, for
  # every finite gate) and its logits are that pass's. The full forward
  # honours any threshold in (0, 1]; the cached decode path refuses one
  # under 1.0 (``loop_refusal``). Each pass runs under ``jax.named_scope``
  # ``pass_<u>``.
  loop_passes: int = 1
  loop_exit_threshold: float = 1.0
  # Attention PER LAYER (ROADMAP R3): ``layer_windows[i]`` is attention layer
  # i's sliding window (0 = full causal; the convention of
  # ``attention_window``, which () leaves in force for every layer) and
  # ``layer_rope[i]`` whether the layer rotates its queries and keys (()
  # = every layer does; False = no positional term at all in that layer).
  # Each attention runs under ``jax.named_scope`` ``attn_window`` /
  # ``attn_full`` when ``layer_windows`` is given.
  layer_windows: tuple = ()
  layer_rope: tuple = ()
  # RMSNorm over each head's ``head_dim`` of the queries and of the keys,
  # before the rotation: one learned scale of ``head_dim`` each, shared by
  # the heads (params ``attn/q_norm``, ``attn/k_norm``).
  qk_norm: bool = False
  # A sigmoid gate on the attention output: ``g = x W_gate`` (``num_heads x
  # head_dim`` wide (the VALUES' width where ``attn_v_head_dim`` gives one),
  # from the layer's normed input, param ``attn/gate``)
  # multiplies the heads' concatenated output elementwise before ``out``.
  attn_gate: bool = False
  # The embedding's output is multiplied by this (computed in float32,
  # rounded once): sqrt(d_model) for a muP-parametrised model.
  embed_scale: float = 1.0
  # The decode cache of a layer with a window holds a RING of the window's
  # rows (``ring_rows``) and not ``max_seq_len``: position p lives in row
  # ``p % rows``, one token a step under per-slot cursors. The serving slab
  # sets it on ITS config for a model with ``layer_windows``, as it sets
  # ``kv_page_size`` (serving/slots.py); the prefill's one-row cache and
  # ``greedy_generate_kv`` keep every position and mask.
  kv_ring: bool = False
  # Heads whose VALUES have another width than their keys: queries and keys
  # are ``head_dim`` wide, values (and each head's output, so ``attn/out``'s
  # input) ``attn_v_head_dim``; 0 = ``head_dim``. The decode cache's K leaf
  # is then ``kv_heads * head_dim`` wide and its V leaf ``kv_heads *
  # attn_v_head_dim``.
  attn_v_head_dim: int = 0
  # KV heads PER LAYER, beside ``layer_windows``: ``layer_kv_heads[i]`` is
  # attention layer i's (0 or () = ``num_kv_heads``'s rule), so a window
  # layer may keep more heads over its few rows than a full layer over all.
  layer_kv_heads: tuple = ()
  # The ROTATED part of a head: the first ``rope_dim`` dims of every query and
  # key head rotate (half-split inside that part), the rest pass; 0 = all of
  # ``head_dim``. ``layer_rope_theta[i]`` is layer i's rotary base (0 or () =
  # ``rope_theta``).
  rope_dim: int = 0
  layer_rope_theta: tuple = ()
  # A learned attention SINK in the layers named: ``layer_sink[i]`` true gives
  # layer i one scalar a query head (param ``attn/sink``, ``[num_heads]``
  # float32) that joins the softmax's DENOMINATOR and nothing else: ``p_ij =
  # exp(s_ij) / (sum_j' exp(s_ij') + exp(sink_h))``, so a head's probabilities
  # sum to less than one. Equivalently ``o = o_plain x sigmoid(lse - sink)``
  # with ``lse`` the plain softmax's log-sum-exp: how the flash partials take
  # it, once, after their last merge. () = no layer has one.
  layer_sink: tuple = ()
  # The values are multiplied by this as they are projected (before the cache
  # holds them): 1.0 = as they come.
  attn_value_scale: float = 1.0
  # Latent attention's QUERY through a rank: ``q = W_qb RMSNorm(W_qa x)``
  # (params ``mla/q_a``, ``mla/q_norm``, ``mla/q_b``) with ``mla_q_rank`` > 0;
  # 0 = one full map (``mla/q``).
  mla_q_rank: int = 0
  # Whether a latent layer ROTATES: the last ``mla_rope_dim`` dims of every
  # query head and the latent's shared key part, at the token's position
  # (half-split inside that part). The cache then holds the key part AS
  # ROTATED, so the absorbed decode reads it as it reads an unrotated one.
  mla_rope: bool = False
  # YaRN beside ``rope_theta``: with ``rope_yarn_factor`` > 0 the frequencies
  # of the rotated dims are blended between ``f_i`` and ``f_i / factor`` by a
  # ramp over the dims whose wavelength passes ``rope_yarn_original``
  # positions between ``beta_fast`` and ``beta_slow`` times
  # (:func:`yarn_frequencies`), and the softmax scale is multiplied by ``(0.1
  # x mscale_all_dim x ln(factor) + 1)^2`` (:func:`yarn_softmax_factor`; 0 =
  # not at all). 0 = ``rope_theta``'s frequencies as they are. Built for the
  # rotated part of latent layers (``mla_rope``).
  rope_yarn_factor: float = 0.0
  rope_yarn_original: int = 0
  rope_yarn_beta_fast: float = 32.0
  rope_yarn_beta_slow: float = 1.0
  rope_yarn_mscale_all_dim: float = 0.0
  # The router's GROUP limit (held experts): the ``experts_total`` experts lie
  # in ``experts_groups`` groups of consecutive experts; a token may choose
  # inside its ``experts_groups_kept`` best groups only, a group's score the
  # sum of its two largest biased scores. 0 = no limit.
  experts_groups: int = 0
  experts_groups_kept: int = 0
  # The router's SCORE (held experts): "sigmoid" = ``s = sigmoid(x W)``, the
  # selection over ``s + bias`` (param ``moe/router_bias``); "softmax" = ``p =
  # softmax(x W)`` over the router's whole width, the ``experts_top_k``
  # largest, no bias (the param does not exist). Either way the weights are
  # the chosen scores over their sum, times ``experts_scale``.
  experts_score: str = "sigmoid"
  # A learned SELECTION of the cached tokens a query attends (attention
  # layers): with ``sparse_topk`` > 0 every attention layer carries an INDEXER
  # (params ``attn/index_q`` ``[d_model, index_heads, index_head_dim]``,
  # ``attn/index_k`` ``[d_model, index_head_dim]`` with a LayerNorm
  # ``attn/index_k_norm`` (scale and bias), ``attn/index_w`` ``[d_model,
  # index_heads]``), all from the layer's normed input: query ``t`` scores
  # position ``s <= t`` ``I(t, s) = sum_h w_t,h relu(rot(qI_t,h) . rot(kI_s))``
  # with ``w_t = x_t W_w index_heads^-0.5 index_head_dim^-0.5`` (float32
  # products of the stored numbers; queries and the one key a token rotate
  # over all ``index_head_dim`` dims at ``rope_theta``), and attends the
  # ``min(t + 1, sparse_topk)`` positions with the largest score alone, the
  # earlier position first among equal scores (:func:`select_topk`: exact).
  # The decode cache holds the rotated index key as a THIRD leaf a layer
  # (``cached_ik`` ``[batch, max_seq_len, 128]``, ``index_head_dim`` lanes
  # used). 0 = every position is attended, no indexer exists: the programs
  # traced before the field existed.
  sparse_topk: int = 0
  index_heads: int = 0
  index_head_dim: int = 0

  def __post_init__(self):
    if self.moe_experts > 0 and self.moe_every < 1:
      raise ValueError("moe_every must be >= 1 when moe_experts > 0")
    if self.attention_impl not in ("auto", "flash", "dense"):
      raise ValueError("attention_impl must be 'auto', 'flash' or 'dense', "
                       "got %r" % (self.attention_impl,))
    if self.layer_norm_impl not in ("auto", "fused", "flax"):
      raise ValueError("layer_norm_impl must be 'auto', 'fused' or 'flax', "
                       "got %r" % (self.layer_norm_impl,))
    if self.num_kv_heads < 0:
      raise ValueError("num_kv_heads must be >= 0, got %d"
                       % (self.num_kv_heads,))
    if self.attention_window < 0:
      raise ValueError("attention_window must be >= 0 (0 = full causal), "
                       "got %d" % (self.attention_window,))
    if self.num_kv_heads and self.num_heads % self.num_kv_heads != 0:
      raise ValueError("num_kv_heads (%d) must divide num_heads (%d)"
                       % (self.num_kv_heads, self.num_heads))
    if self.embed_lookup not in ("gather", "one_hot"):
      raise ValueError("embed_lookup must be 'gather' or 'one_hot', got %r"
                       % (self.embed_lookup,))
    if self.remat_policy not in ("none", "dots"):
      raise ValueError("remat_policy must be 'none' or 'dots', got %r"
                       % (self.remat_policy,))
    if self.kv_cache_dtype not in ("model", "int8"):
      raise ValueError("kv_cache_dtype must be 'model' or 'int8', got %r"
                       % (self.kv_cache_dtype,))
    if self.kv_page_size < 0 or self.kv_num_pages < 0 \
        or self.kv_pages_per_slot < 0:
      raise ValueError("kv_page_size/kv_num_pages/kv_pages_per_slot must "
                       "be >= 0")
    for name, types, known in (
        ("layer_types", self.layer_types, ("attn", "kda", "mla")),
        ("ffn_types", self.ffn_types, ("mlp", "experts"))):
      if types and (len(types) != self.num_layers
                    or any(t not in known for t in types)):
        raise ValueError("%s must name one of %r for each of the %d layers, "
                         "got %r" % (name, known, self.num_layers, types))
    if self.norm not in ("layer", "rms"):
      raise ValueError("norm must be 'layer' or 'rms', got %r" % (self.norm,))
    if self.mlp_act not in ("gelu", "swiglu"):
      raise ValueError("mlp_act must be 'gelu' or 'swiglu', got %r"
                       % (self.mlp_act,))
    if "experts" in self.ffn_types and not (
        0 <= self.experts_first
        and 0 < self.experts_held
        and self.experts_first + self.experts_held <= self.experts_total
        and 0 < self.experts_top_k <= self.experts_total):
      raise ValueError(
          "held experts [%d, %d) must lie inside the router's %d, with "
          "0 < experts_top_k=%d <= that" % (
              self.experts_first, self.experts_first + self.experts_held,
              self.experts_total, self.experts_top_k))
    if self.loop_passes < 1 or not 0.0 < self.loop_exit_threshold <= 1.0:
      raise ValueError(
          "loop_passes must be >= 1 and loop_exit_threshold in (0, 1], got "
          "%r and %r" % (self.loop_passes, self.loop_exit_threshold))
    if self.loop_passes > 1 and (self.non_kv_layers or self.moe_experts
                                 or "experts" in self.ffn_types):
      raise ValueError(
          "loop_passes=%d: only attention + MLP layers keep a cache a pass "
          "(layer_types %r, ffn_types %r, moe_experts %d)" % (
              self.loop_passes, self.layer_types, self.ffn_types,
              self.moe_experts))
    for name, per_layer in (("layer_windows", self.layer_windows),
                            ("layer_rope", self.layer_rope),
                            ("layer_kv_heads", self.layer_kv_heads),
                            ("layer_rope_theta", self.layer_rope_theta),
                            ("layer_sink", self.layer_sink)):
      if per_layer and (len(per_layer) != self.num_layers
                        or any(w < 0 for w in per_layer)):
        raise ValueError("%s must give each of the %d layers a value >= 0, "
                         "got %r" % (name, self.num_layers, per_layer))
    if any(hk and self.num_heads % hk for hk in self.layer_kv_heads):
      raise ValueError("each of layer_kv_heads %r must divide num_heads (%d)"
                       % (self.layer_kv_heads, self.num_heads))
    if self.attn_v_head_dim < 0 or self.rope_dim < 0 or self.rope_dim % 2 \
        or self.rope_dim > self.head_dim:
      raise ValueError(
          "attn_v_head_dim must be >= 0 and rope_dim an even number of a "
          "head's %d dims (0 = all), got %d and %d"
          % (self.head_dim, self.attn_v_head_dim, self.rope_dim))
    for asked, feature, what in (
        (self.kv_ring and self.kv_cache_dtype == "int8", "int8",
         "kv_cache_dtype='int8'"),
        (self.kv_ring and self.loop_passes > 1, "loop",
         "loop_passes=%d" % self.loop_passes),
        (self.kv_page_size > 0 and self.ring_layers, "pages",
         "the paged KV pool (kv_page_size=%d)" % self.kv_page_size)):
      if asked:
        raise ValueError(ring_refusal(what, feature))
    if self.wide_heads:
      for asked, feature, what in (
          (self.kv_cache_dtype == "int8", "int8", "kv_cache_dtype='int8'"),
          (self.kv_page_size > 0, "pages",
           "the paged KV pool (kv_page_size=%d)" % self.kv_page_size),
          (self.use_ring_attention, "mesh", "use_ring_attention")):
        if asked:
          raise ValueError(heads_refusal(self, what, feature))
    if self.kv_cache_dtype == "int8" and (self.mla_rope or self.mla_q_rank):
      raise ValueError(
          "kv_cache_dtype='int8' cannot take a latent layer with a query rank "
          "or a rotated key part (mla_q_rank=%d, mla_rope=%r): the latent leaf "
          "is held in the model's dtype, and an int8 latent (one scale a row "
          "for the normed part, one for the rotated key part) is not built"
          % (self.mla_q_rank, self.mla_rope))
    if self.mla_q_rank < 0 or (self.mla_rope and self.mla_rope_dim % 2):
      raise ValueError(
          "mla_q_rank must be >= 0 and a rotated mla_rope_dim whole pairs, "
          "got %d and %d" % (self.mla_q_rank, self.mla_rope_dim))
    if self.rope_yarn_factor:
      if self.rope_yarn_factor < 1.0 or self.rope_yarn_original < 1 \
          or not self.rope_yarn_beta_fast > self.rope_yarn_beta_slow > 0 \
          or self.rope_yarn_mscale_all_dim < 0:
        raise ValueError(
            "rope_yarn_factor=%r needs a factor >= 1, rope_yarn_original >= 1 "
            "positions, rope_yarn_beta_fast > rope_yarn_beta_slow > 0 and "
            "rope_yarn_mscale_all_dim >= 0, got %r, %r, %r and %r" % (
                self.rope_yarn_factor, self.rope_yarn_original,
                self.rope_yarn_beta_fast, self.rope_yarn_beta_slow,
                self.rope_yarn_mscale_all_dim))
      if not self.mla_rope or set(self.layer_types) != {"mla"}:
        raise ValueError(
            "rope_yarn_factor=%r scales the frequencies and the softmax of "
            "the ROTATED PART OF LATENT LAYERS (layer_types all 'mla' with "
            "mla_rope); this model's layers are %r with mla_rope=%r: an "
            "attention layer's rotary at scaled frequencies, and its kernels "
            "at another softmax scale than head_dim^-0.5, are not built" % (
                self.rope_yarn_factor, self.layer_types or ("attn",),
                self.mla_rope))
    if self.experts_groups or self.experts_groups_kept:
      g, kept = self.experts_groups, self.experts_groups_kept
      per = self.experts_total // g if g > 0 else 0
      if not (g > 0 and self.experts_total % g == 0 and per >= 2
              and 0 < kept <= g and self.experts_top_k <= kept * per):
        raise ValueError(
            "experts_groups=%d must divide the router's %d experts into "
            "groups of at least 2 (a group's score is the sum of its two "
            "largest), with 0 < experts_groups_kept=%d <= that and room for "
            "experts_top_k=%d inside the groups kept" % (
                g, self.experts_total, kept, self.experts_top_k))
    if self.experts_score not in ("sigmoid", "softmax"):
      raise ValueError("experts_score must be 'sigmoid' or 'softmax', got %r"
                       % (self.experts_score,))
    if self.experts_score == "softmax" and self.experts_groups:
      raise ValueError(
          "experts_score='softmax' routes over the router's whole width; a "
          "group limit (experts_groups=%d) ranks groups by the sum of their "
          "two largest BIASED SIGMOID scores, and a group limit over softmax "
          "scores is not built" % self.experts_groups)
    if self.sparse_topk < 0 or (not self.sparse_topk and (
        self.index_heads or self.index_head_dim)):
      raise ValueError(
          "sparse_topk must be >= 0 and index_heads / index_head_dim belong "
          "to a selection (sparse_topk > 0), got %d, %d and %d"
          % (self.sparse_topk, self.index_heads, self.index_head_dim))
    if self.sparse_topk:
      if self.index_heads < 1 or not 0 < self.index_head_dim <= INDEX_LANES \
          or self.index_head_dim % 2:
        raise ValueError(
            "sparse_topk=%d needs index_heads >= 1 and an even index_head_dim "
            "of at most %d (the index leaf's lanes), got %d and %d" % (
                self.sparse_topk, INDEX_LANES, self.index_heads,
                self.index_head_dim))
      for asked, feature, what in (
          (bool(self.non_kv_layers), "layers",
           "layer_types %r" % (self.layer_types,)),
          (bool(self.attention_window or any(self.layer_windows)
                or self.kv_ring), "window",
           "a sliding window or a ring (attention_window=%d, layer_windows "
           "%r)" % (self.attention_window, self.layer_windows)),
          (self.wide_heads, "heads",
           "attn_v_head_dim=%d, layer_kv_heads %r, layer_sink %r" % (
               self.attn_v_head_dim, self.layer_kv_heads, self.layer_sink)),
          (self.loop_passes > 1, "loop", "loop_passes=%d" % self.loop_passes),
          (self.kv_cache_dtype == "int8", "int8", "kv_cache_dtype='int8'"),
          (self.kv_page_size > 0, "pages",
           "the paged KV pool (kv_page_size=%d)" % self.kv_page_size),
          (self.use_ring_attention, "mesh", "use_ring_attention"),
          (self.moe_experts > 0, "aux", "moe_experts=%d (the trained MoE "
           "block)" % self.moe_experts)):
        if asked:
          raise ValueError(sparse_refusal(self, what, feature))
    if self.kv_page_size > 0:
      if self.loop_passes > 1:
        raise ValueError(loop_refusal(
            self, "pages", "the paged KV pool (kv_page_size=%d)" % self.kv_page_size))
      if self.non_kv_layers:
        raise ValueError(
            "the paged KV pool (kv_page_size=%d) holds keys and values per "
            "head in every layer; this model's %s layers cache %s instead "
            "(a paged latent cache or recurrent state does not exist yet)"
            % (self.kv_page_size, "/".join(self.non_kv_layers),
               " and ".join({"kda": "a recurrent state without positions",
                             "mla": "one shared latent a token"}[t]
                            for t in self.non_kv_layers)))
      if self.kv_num_pages < 2:
        raise ValueError(
            "paged KV needs kv_num_pages >= 2 (page 0 is the reserved "
            "trash page), got %d" % (self.kv_num_pages,))
      if self.kv_pages_per_slot < 1:
        raise ValueError("paged KV needs kv_pages_per_slot >= 1, got %d"
                         % (self.kv_pages_per_slot,))
      if self.kv_cache_dtype == "int8":
        raise ValueError("paged KV does not compose with the int8 cache "
                         "yet — use kv_cache_dtype='model'")

  @property
  def head_dim(self) -> int:
    if self.attn_head_dim:
      return self.attn_head_dim
    assert self.d_model % self.num_heads == 0
    return self.d_model // self.num_heads

  @property
  def non_kv_layers(self) -> tuple:
    """The layer types here whose decode cache is NOT keys and values per
    head: "kda" keeps a recurrent state, "mla" one shared latent a token.
    What pages or shares K/V has to refuse them (serving/slots.py)."""
    return tuple(sorted(set(self.layer_types) - {"attn"}))

  @property
  def recurrent_state(self) -> bool:
    """Whether some layer's decode cache has NO position axis ("kda"): a
    cursor rollback or a prefix of pages cannot restore such a state."""
    return "kda" in self.layer_types

  @property
  def kv_heads(self) -> int:
    return self.num_kv_heads or self.num_heads

  @property
  def v_head_dim(self) -> int:
    return self.attn_v_head_dim or self.head_dim

  @property
  def wide_heads(self) -> bool:
    """Whether some attention layer's heads are not ONE width for queries,
    keys and values under ONE KV head count and a plain softmax: values of
    another width, KV heads by layer, or a sink. What takes a head to be
    one ``head_dim`` throughout refuses such a model (``heads_refusal``)."""
    return bool(self.v_head_dim != self.head_dim or any(self.layer_sink)
                or any(hk and hk != self.kv_heads
                       for hk in self.layer_kv_heads))

  def ring_rows(self, window: int) -> int:
    """Rows of the RING a decode cache holds for an attention ``window``, 0
    where the cache keeps every position (no window, or a ring as long as
    the row): the window rounded up to whole blocks of
    ``ops.decode_attention`` (128 rows), a window under one block (toy
    sizes, where the kernel does not engage) to whole 16-row tiles of
    ``ops.cursor_write``."""
    if not window:
      return 0
    tile = 128 if window >= 128 else 16
    rows = -(-int(window) // tile) * tile
    return rows if rows < self.max_seq_len else 0

  @property
  def ring_layers(self) -> tuple:
    """The layers (by index) whose window a serving slab holds as a ring."""
    return tuple(i for i, w in enumerate(self.layer_windows)
                 if self.ring_rows(w))


#: lanes of the decode cache's index-key leaf (``cached_ik``): whole vregs,
#: ``index_head_dim`` of them used
INDEX_LANES = 128

#: why each feature cannot take attention layers that SELECT the cached tokens
#: a query attends (``TransformerConfig.sparse_topk``; ``sparse_refusal``)
_SPARSE_REFUSALS = {
    "layers": "the indexer is built into attention layers (keys and values "
              "per head); a latent or a recurrent layer that selects is not "
              "built",
    "window": "a selection among the positions of a window, and a ring whose "
              "rows are reordered under the index keys, are not built",
    "heads": "the selection's mask is built for heads of one width under one "
             "KV head count and a plain softmax (a sink's share of a selected "
             "softmax, leaves of two widths or head counts are untried)",
    "loop": "an index-key leaf a pass is not built",
    "int8": "an int8 cache beside a bf16 index-key leaf (whose rounding moves "
            "the selection) is not built",
    "pages": "the pool holds K and V pages; a third pool of index keys, and "
             "a selection gathered through a page table, do not exist yet",
    "prefix": "a prefix's pages hold keys and values, not index keys, and "
              "the pool they live in cannot take such a model",
    "draft": "a verify window scores several tokens a lane against per-slot "
             "cursors, and a rejected draft's index keys sit past the cursor: "
             "a selection over a verify window is not built",
    "mesh": "the index scores, the selection and the kernels' keep operand "
            "are laid out for ONE device (a selection over a sharded sequence "
            "or sharded heads is not built)",
    "train": "the flash backward takes no keep operand and the indexer has "
             "no loss of its own (it is trained to match the attention's "
             "distribution, which nothing here computes): a model that "
             "selects is served, not trained",
    "aux": "the trained MoE block is part of the training path",
}


def sparse_refusal(cfg, what: str, feature: str) -> str:
  """The message with which ``what`` (``feature`` its key in
  ``_SPARSE_REFUSALS``) refuses a model whose attention layers select
  ``cfg.sparse_topk`` cached tokens a query."""
  return "%s cannot take a model whose attention attends the %d cached " \
      "tokens a learned indexer chooses (sparse_topk, %d index heads of " \
      "%d): %s" % (what, cfg.sparse_topk, cfg.index_heads,
                   cfg.index_head_dim, _SPARSE_REFUSALS[feature])


#: why each serving feature cannot take a looped model yet (``loop_refusal``)
_LOOP_REFUSALS = {
    "pages": "the pool holds ONE set of K/V pages a layer, and each of this "
             "model's passes keeps keys and values of its own (a pool a "
             "pass does not exist yet)",
    "prefix": "a prefix's pages are one set a layer, and each of this "
              "model's passes keeps keys and values of its own (prefix "
              "pages a pass do not exist yet)",
    "draft": "the shallow-exit draft is a prefix of LAYERS, and a prefix of "
             "this model's layers is not a prefix of its passes: the draft "
             "would skip every layer's later passes and leave their caches "
             "unwritten (a draft of fewer PASSES does not exist yet)",
    "early_exit": "a token that exits early writes no keys for its later "
                  "passes, which later tokens' later passes attend; what "
                  "they read instead is a policy the configuration does not "
                  "state",
}


#: why each serving feature cannot take window layers held as RINGS yet
_RING_REFUSALS = {
    "pages": "the pool gives every layer a page for every position a slot "
             "holds, and a window layer keeps only its last window's rows "
             "(a pool of two lifetimes, whole-context and window, does not "
             "exist yet)",
    "prefix": "a prefix's pages hold every layer's keys and values at every "
              "position of the prefix, and a window layer keeps only its last "
              "window's rows (prefix sharing over window layers does not "
              "exist yet)",
    "draft": "a rejected draft rolls the cursor back, and the rows it wrote "
             "into a ring have overwritten the window's oldest positions, "
             "which the rolled-back step attends (speculation over a ring "
             "does not exist yet)",
    "int8": "an int8 ring (values with a scale leaf a row, both indexed "
            "modulo the ring) is not built",
    "loop": "a ring a pass is not built",
}


#: why each feature cannot take attention heads whose keys and values differ
#: in width, whose KV head count differs by layer, or that carry a sink
_HEADS_REFUSALS = {
    "int8": "an int8 cache quantizes K and V per head under one layout of "
            "scales, untried for leaves of two widths",
    "pages": "the pool's pages are [page, kv_heads, head_dim] for K and V "
             "alike in every layer (pages of two widths or two head counts do "
             "not exist yet)",
    "mesh": "the sharded flash kernel and the ring shard q, k and v under "
            "one spec of one head width and one KV head count (a mesh for "
            "such heads is not built)",
}


def heads_refusal(cfg, what: str, feature: str) -> str:
  """The message with which ``what`` (``feature`` its key in
  ``_HEADS_REFUSALS``) refuses a model with ``cfg.wide_heads``."""
  return "%s cannot take a model whose heads have keys of %d and values of " \
      "%d dims, KV heads %r by layer and sinks %r (attn_v_head_dim, " \
      "layer_kv_heads, layer_sink): %s" % (
          what, cfg.head_dim, cfg.v_head_dim, cfg.layer_kv_heads or None,
          cfg.layer_sink or None, _HEADS_REFUSALS[feature])


def ring_refusal(what: str, feature: str) -> str:
  """The message with which ``what`` (a serving feature, named as its user
  named it; ``feature`` its key in ``_RING_REFUSALS``) refuses a model with
  per-layer windows (``TransformerConfig.layer_windows``)."""
  return "%s cannot serve a model whose window layers keep a ring of their " \
      "window's rows (layer_windows): %s" % (what, _RING_REFUSALS[feature])


def loop_refusal(cfg, feature: str, what: str) -> str:
  """The message with which ``what`` (a serving feature, named as its user
  named it; ``feature`` its key in ``_LOOP_REFUSALS``) refuses a model
  whose layers run ``cfg.loop_passes`` times."""
  return "%s cannot serve a model whose %d layers run %d times a token: %s" \
      % (what, cfg.num_layers, cfg.loop_passes, _LOOP_REFUSALS[feature])


def exit_pass(gates, threshold: float):
  """The pass (1-based) at which each token exits a looped model: ``gates``
  holds the exit gates after passes 1..n-1 (each ``[...]``, in (0, 1)); the
  exit distribution is ``p_u = gate_u prod_{j<u}(1 - gate_j)``, the last
  pass taking what is left, and a token exits at the first pass whose
  cumulative ``p`` reaches ``threshold``: the last for every finite gate
  at 1.0."""
  last = len(gates) + 1
  stay = jnp.ones_like(gates[0])       # prod_{j<=u}(1 - gate_j)
  out = jnp.full(gates[0].shape, last, jnp.int32)
  for u, g in enumerate(gates):
    stay = stay * (1.0 - g)
    out = jnp.where((out == last) & (1.0 - stay >= threshold), u + 1, out)
  return out


def yarn_frequencies(theta: float, dims: int, factor: float, original: int,
                     beta_fast: float = 32.0, beta_slow: float = 1.0):
  """YaRN's ``dims // 2`` rotary frequencies (numpy float64, computed while
  tracing): ``f_i = theta^(-2i/dims)``; a dim turns ``n`` times over
  ``original`` positions at ``d(n) = dims ln(original / (2 pi n)) / (2 ln
  theta)``; ``low = floor(d(beta_fast))``, ``high = ceil(d(beta_slow))``
  (inside ``[0, dims - 1]``), ``r_i = clip((i - low) / (high - low), 0, 1)``;
  the frequency used is ``f_i (1 - r_i) + (f_i / factor) r_i``: fast dims
  keep theirs, slow dims are interpolated, the ramp between blends."""
  half = dims // 2
  f = float(theta) ** (-np.arange(half, dtype=np.float64) / half)

  def turns_at(n):
    return dims * math.log(original / (2 * math.pi * n)) \
        / (2 * math.log(theta))

  low = max(math.floor(turns_at(beta_fast)), 0)
  high = min(math.ceil(turns_at(beta_slow)), dims - 1)
  r = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0.0, 1.0)
  return f * (1.0 - r) + f / factor * r


def yarn_softmax_factor(cfg) -> float:
  """What YaRN multiplies a latent layer's softmax scale by: ``m^2`` with ``m
  = 0.1 x rope_yarn_mscale_all_dim x ln(rope_yarn_factor) + 1``; 1.0 without
  YaRN or with ``rope_yarn_mscale_all_dim`` 0."""
  if cfg.rope_yarn_factor <= 1.0 or not cfg.rope_yarn_mscale_all_dim:
    return 1.0
  return (0.1 * cfg.rope_yarn_mscale_all_dim
          * math.log(cfg.rope_yarn_factor) + 1.0) ** 2


def _rotary(x, positions, theta: float = 10000.0, dims: int = 0, freqs=None):
  """Rotary position embedding over the last (head_dim) axis; with ``dims``
  over its first ``dims`` alone (``TransformerConfig.rope_dim``), the rest
  passing as they are. ``freqs`` (``[d // 2]``, e.g.
  :func:`yarn_frequencies`) replaces ``theta``'s frequencies."""
  if dims and dims < x.shape[-1]:
    return jnp.concatenate(
        [_rotary(x[..., :dims], positions, theta), x[..., dims:]], axis=-1)
  d = x.shape[-1]
  half = d // 2
  freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                  * (jnp.log(theta) / half)) if freqs is None \
      else jnp.asarray(freqs, jnp.float32)
  angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,half]
  cos = jnp.cos(angles)[:, :, None, :]
  sin = jnp.sin(angles)[:, :, None, :]
  x1, x2 = x[..., :half], x[..., half:]
  return jnp.concatenate(
      [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def _flash_tiles(seq_len: int) -> bool:
  """Whether ``seq_len`` divides into blocks the flash kernels can take:
  whole 128-blocks, or — below 128, where the one block IS the sequence —
  a multiple of 8. The second clause is the chip's: Mosaic refuses a
  block whose second-minor dim is not sublane-aligned ("cannot statically
  prove that index in dimension 1 is a multiple of 8", first seen when a
  63-token prompt reached the prefill kernel on a v5e); interpret mode
  never minded."""
  return seq_len >= 8 and seq_len % 8 == 0 \
      and seq_len % min(128, seq_len) == 0


def _flash_eligible(cfg: TransformerConfig, seq_len: int) -> bool:
  """Whether the Pallas flash kernel should handle this attention.

  "auto" uses the kernel on TPU only; "flash" FORCES it everywhere —
  interpret mode off-TPU, which is how CPU CI trains through the
  production attention path (same convention as ``layer_norm_impl``);
  "dense" always opts out. Either way the sequence must divide into
  kernel blocks.
  """
  if cfg.attention_impl == "dense":
    return False
  divisible = _flash_tiles(seq_len)
  if cfg.attention_impl == "flash":
    if not divisible:
      # forcing must be honest: never silently degrade to dense
      raise ValueError(
          "attention_impl='flash' but the (local) sequence length %d does "
          "not divide into kernel blocks — pad the sequence or use 'auto'"
          % seq_len)
    return True
  # "auto" = the kernel wherever kernels are in play: the TPU backend
  # (even with interpret forced on for numerics debugging), or under
  # TOS_PALLAS_INTERPRET=0 (the deviceless Mosaic gate compiling FOR a
  # TPU topology from a CPU client — it must compile what the chip runs)
  return ops.pallas_kernels_enabled() and divisible


def _fused_ln_eligible(cfg: TransformerConfig) -> bool:
  """Whether blocks should use the fused Pallas LayerNorm ("auto" follows
  the same kernels-in-play policy as attention, see _flash_eligible)."""
  if cfg.layer_norm_impl == "flax":
    return False
  if cfg.layer_norm_impl == "fused":
    return True
  return ops.pallas_kernels_enabled()


class FusedLayerNorm(nn.Module):
  """LayerNorm via the fused Pallas kernel (ops.layer_norm).

  Same parameter ("scale"), stats dtype (f32) and eps as the flax
  ``nn.LayerNorm(use_bias=False)`` it replaces, so checkpoints are
  interchangeable across ``layer_norm_impl`` settings. With a mesh the
  kernel maps per-shard through shard_map (ops.layer_norm_sharded) — an
  unpartitioned pallas_call over GSPMD-sharded activations would force
  gathers (ROADMAP: ops coverage).
  """
  mesh: Optional[Any] = None
  eps: float = 1e-6
  interpret: bool = False

  @nn.compact
  def __call__(self, x):
    from tensorflowonspark_tpu import ops
    w = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                   jnp.float32)
    # x goes in at its native dtype — the kernel computes f32 statistics
    # internally, so upcasting here would only double the HBM read traffic
    # (the downstream matmuls cast to cfg.dtype regardless)
    if self.mesh is not None:
      return ops.layer_norm_sharded(x, w, self.mesh, eps=self.eps,
                                    interpret=self.interpret)
    return ops.layer_norm(x, w, eps=self.eps, interpret=self.interpret)


def _make_layer_norm(cfg: TransformerConfig, mesh, name: str):
  if cfg.norm == "rms":
    return nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32, name=name)
  if _fused_ln_eligible(cfg):
    return FusedLayerNorm(mesh=mesh, name=name,
                          interpret=ops.pallas_interpret())
  return nn.LayerNorm(dtype=jnp.float32, use_bias=False, name=name)


# grouped-KV head broadcast: ONE definition, shared with the ring
# (parallel.ring_attention.expand_heads) so the grouping convention
# (blocked: KV head j serves query heads [j*g, (j+1)*g)) cannot drift
_expand_kv = ra.expand_heads


def _heads_logical(n_heads: int, mesh) -> Optional[str]:
  """The logical axis for a heads dimension: "heads" (→ the
  tensor-parallel mesh axis) when the head count divides the tensor axis,
  else None (replicated). ONE rule shared by the projection kernels and
  the KV-cache constraint — a head count the axis can't divide (grouped
  KV heads) must fall back to replication on BOTH sides or params and
  cache shard inconsistently (GSPMD then gathers the cache every decode
  step)."""
  t = 1 if mesh is None else mesh.shape.get(mesh_lib.AXIS_TENSOR, 1)
  return "heads" if n_heads % max(1, t) == 0 else None


#: columns of one MXU pass (v5e: 128 x 128). The folded decode attention
#: expands q block-diagonally over the KV heads; the zeros of that expansion
#: cost nothing while the expanded operand still fits ONE pass, and past it
#: they cost kv_heads times the arithmetic, so wider query blocks (prefill
#: chunks against their one-row cache) take the 4-D contraction instead
_MXU_COLS = 128


_cursor_writes = threading.local()
_attn_reads = threading.local()
_expert_products = threading.local()
_index_selects = threading.local()


@contextlib.contextmanager
def _tally(local, *keys):
  """Open one round of trace-time counting on this thread: the traced code
  finds the dict at ``local.open`` and notes into it on the host (the
  traced program never contains the note)."""
  tally = local.open = dict.fromkeys(keys, 0)
  try:
    yield tally
  finally:
    local.open = None


def cursor_write_tally():
  """Count the per-slot single-token cache writes of what is TRACED inside
  the block, on this thread: yields ``{"leaves": n, "dma": m}``, ``m`` of
  the ``n`` having taken ``ops.cursor_write``'s kernel. ``_cache_write``
  notes each one while it traces; ``SlotDecoder`` opens one round a
  ``step_many`` program's trace."""
  return _tally(_cursor_writes, "leaves", "dma")


def decode_attention_tally():
  """The same for the per-slot single-token cache READS
  (``_cached_attention`` with ``lengths``, one a layer application):
  yields ``{"reads": n, "ragged": m, "ring": r}``, ``m`` of the ``n`` having
  taken ``ops.decode_attention``'s kernel, which stops at each slot's
  cursor, and ``r`` of the ``n`` reading a RING leaf
  (``TransformerConfig.kv_ring``); where a read lay under a selection's keep
  mask (``TransformerConfig.sparse_topk``) a fourth key ``"sparse"`` counts
  those (each also read its layer's index-key leaf whole: every slot's
  ``max_seq_len`` rows)."""
  return _tally(_attn_reads, "reads", "ragged", "ring")


def expert_product_tally():
  """The same for the grouped products of the held experts
  (``parallel.expert_parallel.held_experts_ffn``: gate, up and down, three a
  layer application): yields ``{"products": n, "kernel": m}``, ``m`` of the
  ``n`` having taken ``ops.expert_product``'s kernel, which reads only the
  rows that have a group, and not ``lax.ragged_dot``. ``SlotDecoder`` opens
  one round a ``step_many`` program's trace and one a prefill shape's."""
  return _tally(_expert_products, "products", "kernel")


def index_select_tally():
  """The same for the indexer's exact selections (:func:`select_topk`, one a
  layer application of a model whose attention selects): yields
  ``{"selections": n, "kernel": m}``, ``m`` of the ``n`` searches having run
  in ``ops.select_topk``'s kernel and not as XLA operations. ``SlotDecoder``
  opens one round a ``step_many`` program's trace and one a prefill shape's."""
  return _tally(_index_selects, "selections", "kernel")


def _cache_write(buf, val, idx, positions, mesh):
  """Write ``val [b, seg, c]`` into the cache leaf ``buf [b, max, c]`` at
  the cursor ``idx``: one dynamic_update_slice for the shared scalar
  cursor; for per-slot cursors and ONE token (the serving decode step) one
  row a slot, by ``ops.cursor_write``'s DMA kernel where the leaf and the
  device allow it and by a vmapped update-slice elsewhere: one write, two
  lowerings, chosen from what the code can observe.

  Multi-token per-row writes go through an explicit OOB-dropping
  scatter instead: a speculative verify window may transiently
  overshoot ``max_seq_len`` on a lane whose remaining budget is
  smaller than the draft depth, and dynamic_update_slice would
  CLAMP the start — silently overwriting live attended KV below the
  cursor (breaking bit-parity) instead of dropping the overflow
  (which is never attended: accepted tokens stay within budget)."""
  b, seg = val.shape[:2]
  if idx.ndim == 0:
    return jax.lax.dynamic_update_slice(buf, val, (0, idx, 0))
  if seg == 1:
    # single-token decode can never overshoot (cursor < max_seq_len by the
    # submit-time budget check), and a frozen lane's cursor == max clamps
    # onto its own last row in either lowering. The kernel: a lane-dense
    # leaf of whole row tiles, on ONE device (GSPMD does not partition a
    # Mosaic call), where "auto" picks Pallas kernels at all. XLA lowers
    # the vmap to a loop of b bounds-checked update-slices a leaf, a third
    # of a GPT-2 decode step's device time (PERF.md section 6, PR 29); a
    # native scatter or b unrolled slices make the compiler write the leaf
    # back whole from fast memory (PR 25)
    dma = ((mesh is None or mesh.size == 1)
           and ops.cursor_write_supports(buf.shape, buf.dtype)
           and ops.pallas_kernels_enabled())
    tally = getattr(_cursor_writes, "open", None)
    if tally is not None:
      tally["leaves"] += 1
      tally["dma"] += dma
    if dma:
      return ops.cursor_write(buf, val[:, 0], idx,
                              interpret=ops.pallas_interpret())
    return jax.vmap(
        lambda row, v, i: jax.lax.dynamic_update_slice(
            row, v, (i, 0)))(buf, val, idx)
  rows = jnp.broadcast_to(jnp.arange(b)[:, None], (b, seg)).reshape(-1)
  pos = positions.reshape(-1)          # OOB entries drop, not clamp
  return buf.at[rows, pos].set(val.reshape(b * seg, val.shape[2]))


def _bf16_terms(x):
  """``x`` as bf16 arrays that SUM to it: itself when it is bf16, else
  three terms (8 + 8 + 8 significant bits hold an f32's 24).
  ``reduce_precision`` and not a convert pair: XLA may elide
  f32->bf16->f32 (``xla_allow_excess_precision``), and the remainder
  would silently read 0."""
  if x.dtype == jnp.bfloat16:
    return [x]
  terms, rest = [], x.astype(jnp.float32)
  for _ in range(3):
    t = lax.reduce_precision(rest, exponent_bits=8, mantissa_bits=7)
    terms.append(t.astype(jnp.bfloat16))
    rest = rest - t
  return terms


def _cache_contract(eq, small, cache):
  """``einsum(eq, small, cache)`` in f32 with NEITHER operand rounded.

  ``cache`` is a whole KV buffer ``[b, max, c]`` and is read as stored:
  bf16 (or int8 values, which bf16 holds exactly) meets ``small``
  ``[b, n, x]`` as bf16 terms stacked along ``n``; bf16 x bf16 products
  are exact in f32, so one MXU pass accumulating in f32 IS the f32
  contraction (no f32 copy of the cache, no six-pass ``HIGHEST``). Any
  other cache dtype takes the f32 contraction itself."""
  if cache.dtype not in (jnp.bfloat16, jnp.int8):
    return jnp.einsum(eq, small.astype(jnp.float32),
                      cache.astype(jnp.float32),
                      precision=lax.Precision.HIGHEST)
  terms = _bf16_terms(small)
  out = jnp.einsum(eq, jnp.concatenate(terms, axis=1),
                   cache.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
  n = small.shape[1]
  return sum(out[:, i * n:(i + 1) * n] for i in range(len(terms)))


def _weight_matmul(eq, x, w, cfg, f32_out: bool = False):
  """``einsum(eq, x, w)`` of an activation with a WEIGHT of the typed layers
  (``x``'s subscripts must not use ``z``). Without ``cfg.act_f32`` both
  meet in the compute dtype (what ``nn.Dense(dtype=cfg.dtype)`` does; the
  result stays in it unless ``f32_out``). With it the result is float32
  and the activation is not rounded: against a bf16 weight ``x`` goes in
  as its three bf16 terms stacked along a new leading axis
  (``_bf16_terms``): bf16 x bf16 products are exact in f32, so one matmul
  of three times the rows IS the float32 activation times the bf16 weight;
  against a float32 weight (an init, a test) it is the f32 product."""
  lhs, rest = eq.split(",")
  rhs, out = rest.split("->")
  if cfg.act_f32 and w.dtype == jnp.bfloat16:
    parts = jnp.stack(_bf16_terms(x))
    return jnp.einsum("z%s,%s->z%s" % (lhs, rhs, out), parts, w,
                      preferred_element_type=jnp.float32).sum(axis=0)
  if cfg.act_f32:            # float32 weights (an init, a test): no rounding
    return jnp.einsum(eq, x.astype(jnp.float32), w.astype(jnp.float32),
                      precision=lax.Precision.HIGHEST)
  return jnp.einsum(eq, x.astype(cfg.dtype), w.astype(cfg.dtype),
                    preferred_element_type=jnp.float32 if f32_out else None)


def _act_einsum(eq, a, b, cfg):
  """``einsum`` of two ACTIVATIONS in the typed layers, accumulated in f32:
  at full float32 precision under ``cfg.act_f32`` (neither side is a
  stored bf16 number), as the operands come otherwise."""
  if cfg.act_f32:
    return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=lax.Precision.HIGHEST)
  return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


class Proj(nn.Module):
  """``x @ kernel`` over ``x``'s last ``in_dims`` axes onto ``features``,
  for the typed layers: the param sits where ``nn.Dense`` /
  ``nn.DenseGeneral`` would put it (``<name>/kernel``), the product goes
  through :func:`_weight_matmul` (``cfg.act_f32``)."""
  cfg: TransformerConfig
  features: tuple
  in_dims: int = 1

  @nn.compact
  def __call__(self, x):
    n_in, n_out = self.in_dims, len(self.features)
    kernel = self.param(
        "kernel", nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=tuple(range(n_in)),
            out_axis=tuple(range(n_in, n_in + n_out))),
        tuple(x.shape[-n_in:]) + tuple(self.features), jnp.float32)
    ins, outs = "abc"[:n_in], "uvw"[:n_out]
    return _weight_matmul("...%s,%s%s->...%s" % (ins, ins, outs, outs), x,
                          kernel, self.cfg)


def ring_positions(cursor, rows: int):
  """The position each row of a ring of ``rows`` rows holds when the next
  token's is ``cursor [...]``: ``[..., rows]``, row ``r`` the newest position
  ``p < cursor`` with ``p % rows == r``; negative where the row was never
  written."""
  last = cursor[..., None] - 1
  return last - jnp.mod(last - jnp.arange(rows), rows)


def _ring_skip(cursor, rows: int, window: int):
  """``[2, b]``: the first row and the count of the cyclic run of a ring's
  rows that hold positions OUTSIDE the window of the query at ``cursor``.
  The ring holds positions ``max(cursor - rows, 0) .. cursor - 1``, the
  oldest in row ``oldest % rows``; the query attends those above ``cursor -
  window``. With ``rows == window`` that is the one row the step is about
  to overwrite (position ``cursor - rows``), once the ring is full."""
  oldest = jnp.maximum(cursor - rows, 0)
  return jnp.stack([oldest % rows,
                    jnp.clip(cursor - window - oldest + 1, 0, rows)])


def _sink_rescale(out, lse, sink):
  """A plain softmax's output ``out [b, s, h, dv]`` with log-sum-exp ``lse
  [b, h, s]`` turned into the softmax with a SINK's: ``sink [h]`` joins each
  head's denominator, ``o = o_plain x sigmoid(lse - sink)``
  (``TransformerConfig.layer_sink``). Applied ONCE, after the last merge of
  partials: the sink is no key of any block."""
  keep = jax.nn.sigmoid(lse - sink.astype(jnp.float32)[None, :, None])
  return out.astype(jnp.float32) * jnp.swapaxes(keep, 1, 2)[..., None]


def _full_attention_sink(q, k, v, window, sink):
  """``ring_attention.full_attention`` (causal, the dense reference: K and V
  at the full head count) for a layer with a sink: the same masked scores,
  their softmax rescaled by the sink's share (``_sink_rescale``)."""
  s, d = q.shape[1], q.shape[3]
  scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                      k.astype(jnp.float32)) / (d ** 0.5)
  at = jnp.arange(s)
  keep = at[None, :] <= at[:, None]
  if window:
    keep = jnp.logical_and(keep, at[None, :] > at[:, None] - window)
  scores = jnp.where(keep[None, None], scores, ra.NEG_INF)
  out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1),
                   v.astype(jnp.float32))
  return _sink_rescale(out, jax.nn.logsumexp(scores, axis=-1),
                       sink).astype(q.dtype)


def _flash_attention_sink(q, k, v, window, sink, interpret):
  """The flash FORWARD of a whole (fresh) block for a layer with a sink: the
  block partial's ``(o, lse)`` rescaled once (``_sink_rescale``)."""
  return _sink_rescale(*ops.flash_attention_block(
      q, k, v, 0, 0, causal=True, interpret=interpret,
      window=window or None), sink).astype(q.dtype)


#: the float32 products ``[seg, heads, n]`` of the indexer's queries and a
#: row's keys are made whole up to this many bytes, a ``_ROW_BLOCK`` of keys at
#: a time above it (a 4096-token chunk over 32768 keys would be 8.6 GB)
_INDEX_SCORE_BYTES = 1 << 28


def select_topk(scores, last, k: int, mesh=None):
  """The EXACT top-``k`` of each row as a mask: ``scores [..., n]`` float32,
  ``last [...]`` integers, each row's last candidate (its candidates are the
  columns ``0..last``); returns ``[..., n]`` bool, true at the ``min(k,
  candidates)`` candidates with the largest score, the EARLIER position first
  among equal scores (``lax.top_k``'s order). A row with at most ``k``
  candidates keeps them all. A caller that has nothing else may hand the
  candidates as ``[..., n]`` bool in place of ``last``.

  No sort and no gather: a float's bits, read as an unsigned integer with the
  sign folded, order as the floats do, and the ``k``-th largest of a row is
  the largest threshold ``T`` with ``count(key >= T) >= k``: found bit by bit
  from the top, 32 passes of compare-and-count. One search, two homes for its
  passes, chosen from what the code can observe: float32 scores whose ``n`` is
  whole lanes, with each row's ``last``, on one device (``mesh``) go to
  ``ops.select_topk``'s kernel, which holds a tile of rows in VMEM for all 32
  (``ops.select_topk_supports``); everything else keeps them as XLA
  operations, below, each one pass over the row in whatever memory it lies.
  Those read HALF a key each: the upper 16 bits of ``T`` come from the keys'
  upper halves alone, the lower 16 from the lower halves of the keys whose
  upper half equals ``T``'s (the others count as 0), so a pass moves two bytes
  an entry. Keys above ``T`` stay; of the keys EQUAL to it the first ``k -
  count(key > T)`` by position (a running count, taken only where some row has
  more equals than it needs: rare with real scores). The mask comes out where
  a kernel's operand wants it, with no scatter of indices."""
  by_mask = last.dtype == jnp.bool_
  kernel = not by_mask and ops.select_topk_supports(
      scores.shape, scores.dtype, mesh)
  tally = getattr(_index_selects, "open", None)
  if tally is not None:
    tally["selections"] += 1
    tally["kernel"] += kernel
  if kernel:
    return ops.select_topk(scores, last, k, interpret=ops.pallas_interpret())
  valid = last if by_mask \
      else jnp.arange(scores.shape[-1]) <= last[..., None]
  scores = scores.astype(jnp.float32)
  bits = lax.bitcast_convert_type(       # -0.0 is the score 0.0
      jnp.where(scores == 0, 0.0, scores), jnp.uint32)
  neg = bits >> 31 == 1
  key = jnp.where(neg, ~bits, bits | jnp.uint32(1 << 31))
  # no candidate's key is 0 (that is a NaN with every bit set)
  key = jnp.where(valid, key, jnp.uint32(0))
  kk = jnp.minimum(jnp.sum(valid, axis=-1, keepdims=True, dtype=jnp.int32), k)

  def kth(half, want):
    """The largest 16-bit ``T`` with ``count(half >= T) >= want`` a row."""
    def one_bit(i, t):
      cand = t | (jnp.uint16(1) << (15 - i).astype(jnp.uint16))
      n = jnp.sum(half >= cand, axis=-1, keepdims=True, dtype=jnp.int32)
      return jnp.where(n >= want, cand, t)
    return lax.fori_loop(0, 16, one_bit, jnp.zeros(want.shape, jnp.uint16))

  upper = (key >> 16).astype(jnp.uint16)
  t_hi = kth(upper, kk)
  tied_hi = upper == t_hi
  left = kk - jnp.sum(upper > t_hi, axis=-1, keepdims=True, dtype=jnp.int32)
  t_lo = kth(jnp.where(tied_hi, key.astype(jnp.uint16), jnp.uint16(0)), left)
  t = t_hi.astype(jnp.uint32) << 16 | t_lo.astype(jnp.uint32)
  t = jnp.maximum(t, jnp.uint32(1))       # a row with no candidate keeps none
  above, equal = key > t, key == t
  need = kk - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
  tied = jnp.any(jnp.sum(equal, axis=-1, keepdims=True, dtype=jnp.int32)
                 > need)
  first = lax.cond(
      tied,
      lambda: jnp.cumsum(equal, axis=-1, dtype=jnp.int32) <= need,
      lambda: jnp.ones(equal.shape, jnp.bool_))
  return jnp.logical_or(above, jnp.logical_and(equal, first))


def index_scores(iq, iw, keys, live=None):
  """The indexer's scores ``I [b, seg, n]`` (float32) of ``seg`` queries over
  ``n`` index keys: ``iq [b, seg, heads, di]`` (rotated), ``iw [b, seg,
  heads]`` float32 (scaled), ``keys [b, n, lanes]`` (rotated; the cache leaf
  as stored, ``di`` of its lanes used, the rest zeros): ``sum_h iw_h relu(iq_h
  . key)``. bf16 operands meet exactly in float32 (one MXU pass); float32
  ones (a test) at full precision. The ``[b, seg, heads, n]`` products of a
  long row are made a block of ``_ROW_BLOCK`` keys at a time, up to the block
  that holds position ``live - 1`` (a traced scalar; None = all): the columns
  past it read 0 and are nobody's candidates."""
  b, seg, hi, di = iq.shape
  n, lanes = keys.shape[1:]
  iq = jnp.pad(iq, ((0, 0),) * 3 + ((0, lanes - di),)).astype(keys.dtype)
  exact = lax.Precision.HIGHEST if keys.dtype == jnp.float32 else None

  def block(kb):
    s = jnp.einsum("bqhc,bkc->bqhk", iq, kb, precision=exact,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * iw[..., None], axis=2)

  if n <= _ROW_BLOCK or n % _ROW_BLOCK \
      or seg * hi * n * 4 <= _INDEX_SCORE_BYTES:
    return block(keys)

  def one(j, acc):
    kb = lax.dynamic_slice_in_dim(keys, j * _ROW_BLOCK, _ROW_BLOCK, axis=1)
    return lax.dynamic_update_slice_in_dim(acc, block(kb), j * _ROW_BLOCK,
                                           axis=2)

  blocks = n // _ROW_BLOCK if live is None \
      else jnp.minimum((live - 1) // _ROW_BLOCK + 1, n // _ROW_BLOCK)
  return lax.fori_loop(0, blocks, one, jnp.zeros((b, seg, n), jnp.float32))


def _full_attention_keep(q, k, v, keep):
  """The dense reference attention (K and V at the full head count) under a
  mask by QUERY: ``keep [b, s, s]`` bool, shared by the heads."""
  d = q.shape[3]
  scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                      k.astype(jnp.float32),
                      precision=lax.Precision.HIGHEST) / (d ** 0.5)
  probs = jax.nn.softmax(jnp.where(keep[:, None], scores, ra.NEG_INF), axis=-1)
  return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32),
                    precision=lax.Precision.HIGHEST).astype(q.dtype)


def _cached_attention(q, k, v, cached_k, cached_v, q_pos, window: int = 0,
                      k_scale=None, v_scale=None, lengths=None, mesh=None,
                      ring: bool = False, sink=None, keep=None):
  """Masked softmax attention of a query block over a KV cache AND the
  block's own keys/values, which the cache does not hold yet.

  ``q [b, seg, h, d]`` (rotated), ``k`` / ``v`` ``[b, seg, kv_heads, d]``
  the block's own keys and values AS THE CACHE WILL HOLD THEM (rotated;
  dequantized int8 for an int8 cache); ``cached_k`` / ``cached_v``
  ``[b, max, kv_heads * d]`` — the cache's stored, lane-dense layout, as it
  was BEFORE this block is written; ``q_pos [b | 1, seg]`` each query's
  absolute position. A query attends the cache entries before the block's
  first position (later ones are unwritten or stale) and the block's
  entries up to its own; ``window`` > 0 masks entries older than the
  window in both. Query head i reads KV head i // g. int8 caches pass
  their ``[b, max, kv_heads]`` scales, applied to K-INDEXED tensors —
  scores (sum_d q·k8·s[k] = (sum_d q·k8)·s[k]) and probs (folding v's
  scale) — so no dequantized cache-sized tensor exists in the program.

  Reading the cache as it was keeps the read and the block's write
  INDEPENDENT: the compiler stages each cache leaf in fast memory for the
  big contraction, and a leaf that was written there first has to be
  copied back whole (3 GB a decode step at gpt2-large's widths: half of
  the step, PERF.md section 6, PR 25); a leaf that is only read is not.

  A narrow query block (decode steps, a speculative verify window)
  contracts against the cache AS STORED: q is expanded block-diagonally to
  ``[b, seg*h, kv_heads*d]`` (head i's d values in KV head i//g's block,
  zeros elsewhere), so scores are one batched matmul over the folded axis
  and the output is each head's own block of ``probs @ V``. No 4-D view of
  the cache exists, so the compiler has nothing to relayout: on the TPU a
  ``[.., kv_heads, 64]`` view pads 64 to 128 lanes and copies the slab in
  and out of that layout at every program's edge. A wide block reshapes
  its (one-row) cache to 4-D instead. Probabilities stay f32 all the way
  into V either way.

  ``lengths [b]`` are per-slot cursors (the serving slab's decode step: slot
  ``i``'s cache holds ``lengths[i]`` rows, its query sits at that position).
  With them and ONE token a slot the same attention has a second lowering,
  ``ops.decode_attention``: a kernel that brings only the blocks of live
  rows from HBM where the contraction below reads all ``max`` positions and
  masks (three quarters of a GPT-2 serving step's K/V bytes: PERF.md section
  6, PR 31). Chosen from what the code can observe, as ``_cache_write``
  chooses: bf16 leaves of whole lanes and whole blocks, no window, no int8
  scales, ONE device (GSPMD does not partition a Mosaic call), where "auto"
  picks Pallas kernels at all; every other input keeps the dense path.

  ``ring``: the cache is a RING of ``max`` rows (``TransformerConfig.
  kv_ring``; one token, per-slot cursors): row ``r`` holds the newest
  position ``p < cursor`` with ``p % max == r``. Keys carry their rotary
  position and a softmax does not care in which order it meets its keys, so
  the ring is read as it lies; what has to go is what the window excludes:
  with ``max == window`` the ONE row that the step is about to overwrite
  (position ``cursor - max``), which the kernel takes as a run of rows to
  skip (``_ring_skip``) and the dense path as a mask over each row's
  position.

  The VALUES may have another width than the keys (``v`` ``[b, seg, kv_heads,
  dv]``, ``cached_v`` ``[b, max, kv_heads * dv]``; ``TransformerConfig.
  attn_v_head_dim``): the scores contract over ``d``, the output is ``dv``
  wide. ``sink [h]`` (``TransformerConfig.layer_sink``) joins each head's
  softmax DENOMINATOR beside the cache's and the block's own entries, in
  both lowerings.

  ``keep`` (``TransformerConfig.sparse_topk``): a mask BY QUERY beside the
  positional one, shared by the heads: ``(keep_cache [b, seg, max],
  keep_own [b, seg, seg])`` bool over the cache's rows and the block's own
  entries; a query attends an entry only where both masks allow it (its own
  entry too: a selection may drop it). The kernel takes the cache's part as
  one more operand a slot and the own part as a scalar a slot.
  """
  b, seg, h, d = q.shape
  dv = v.shape[-1]
  mx = cached_k.shape[1]
  if lengths is not None and seg == 1:
    ragged = ((ring or not window) and k_scale is None
              and (mesh is None or mesh.size == 1)
              and ops.decode_attention_supports(
                  (b, h, d), q.dtype, cached_k.shape, cached_k.dtype,
                  cached_v.shape, keep=keep is not None)
              and ops.pallas_kernels_enabled())
    tally = getattr(_attn_reads, "open", None)
    if tally is not None:
      tally["reads"] += 1
      tally["ragged"] += ragged
      tally["ring"] += bool(ring)
      if keep is not None:
        tally["sparse"] = tally.get("sparse", 0) + 1
    if ragged:
      return ops.decode_attention(
          q[:, 0], k[:, 0], v[:, 0], cached_k, cached_v,
          jnp.minimum(lengths, mx) if ring else lengths,
          skip=_ring_skip(lengths, mx, window) if ring else None,
          sink=sink,
          keep=None if keep is None else (keep[0][:, 0], keep[1][:, 0, 0]),
          interpret=ops.pallas_interpret())[:, None].astype(q.dtype)
  hk = cached_k.shape[2] // d
  g = h // hk
  folded = seg * h <= _MXU_COLS
  scale = 1.0 / (d ** 0.5)
  qg = q.reshape(b, seg, hk, g, d).astype(jnp.float32)
  if folded:
    # own[i, j] = 1 where query head i reads KV head j
    own = jnp.repeat(jnp.eye(hk, dtype=jnp.float32), g,
                     axis=0)[None, None, :, :, None]
    q_bd = (q[:, :, :, None, :] * own.astype(q.dtype)).reshape(
        b, seg * h, hk * d)
    s_cache = _cache_contract("bnc,bkc->bnk", q_bd, cached_k)
  else:
    s_cache = jnp.einsum(
        "bqhgd,bkhd->bqhgk", qg,
        cached_k.reshape(b, mx, hk, d).astype(jnp.float32))
  s_cache = s_cache.reshape(b, seg, h, mx) * scale

  def per_head(s):               # [b, max, hk] -> [b, 1, h, max]
    return jnp.repeat(s.transpose(0, 2, 1), g, axis=1)[:, None]

  if k_scale is not None:
    s_cache = s_cache * per_head(k_scale)
  # the block against itself: small, so plainly in f32
  s_own = jnp.einsum("bqhgd,bkhd->bqhgk", qg, k.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
  s_own = s_own.reshape(b, seg, h, seg) * scale

  k_pos, own_pos = jnp.arange(mx), jnp.arange(seg)
  if ring:
    k_pos = ring_positions(q_pos[:, :1], mx)             # [b, 1, max]
  keep_cache = k_pos < q_pos[:, :1, None]       # written before the block
  if ring:
    keep_cache = jnp.logical_and(keep_cache, k_pos >= 0)
  causal = own_pos[None, :] <= own_pos[:, None]
  if window:
    # sliding window: entries older than the window are masked (they stay
    # in the cache buffer; the mask is what bounds decode)
    keep_cache = jnp.logical_and(keep_cache,
                                 k_pos > q_pos[..., None] - window)
    causal = jnp.logical_and(causal,
                             own_pos[None, :] > own_pos[:, None] - window)
  if keep is not None:
    keep_cache = jnp.logical_and(keep_cache, keep[0])
    causal = jnp.logical_and(causal[None], keep[1])        # [b, seg, seg]
  s_cache = jnp.where(keep_cache[:, :, None, :], s_cache, -1e30)
  s_own = jnp.where(causal[None, :, None, :] if keep is None
                    else causal[:, :, None, :], s_own, -1e30)
  # ONE softmax over both parts (a query always keeps its own entry, or,
  # under a selection, at least one entry)
  top = jnp.maximum(s_cache.max(axis=-1), s_own.max(axis=-1))[..., None]
  if sink is not None:
    sink = sink.astype(jnp.float32)[None, None, :, None]  # [1, 1, h, 1]
    top = jnp.maximum(top, sink)
  e_cache, e_own = jnp.exp(s_cache - top), jnp.exp(s_own - top)
  total = e_cache.sum(axis=-1) + e_own.sum(axis=-1)      # [b, seg, h]
  if sink is not None:
    total = total + jnp.exp(sink - top)[..., 0]
  if v_scale is not None:
    e_cache = e_cache * per_head(v_scale)
  if folded:
    o = _cache_contract("bnk,bkc->bnc", e_cache.reshape(b, seg * h, mx),
                        cached_v)
    o = (o.reshape(b, seg, h, hk, dv) * own).sum(axis=3)
  else:
    o = jnp.einsum(
        "bqhgk,bkhd->bqhgd", e_cache.reshape(b, seg, hk, g, mx),
        cached_v.reshape(b, mx, hk, dv).astype(jnp.float32)).reshape(
            b, seg, h, dv)
  o = o + jnp.einsum(
      "bqhgk,bkhd->bqhgd", e_own.reshape(b, seg, hk, g, seg),
      v.astype(jnp.float32),
      precision=lax.Precision.HIGHEST).reshape(b, seg, h, dv)
  return (o / total[..., None]).astype(q.dtype)


#: rows of its one-row cache a later prefill chunk attends a call of the
#: flash kernel (``Attention._decode_attend``): K and V blocks of 0.5 MB at
#: 8 KV heads of 128. A row no longer than one block keeps the dense branch
_ROW_BLOCK = 2048


class Attention(nn.Module):
  """``window`` / ``rope``: this layer's sliding window (None:
  ``cfg.attention_window``) and whether it rotates its queries and keys
  (``TransformerConfig.layer_windows`` / ``layer_rope``); ``kv_heads`` /
  ``theta``: its KV head count and rotary base where they are the layer's own
  (``layer_kv_heads`` / ``layer_rope_theta``; 0 = the model's); ``sink``:
  whether its softmax has a learned sink (``layer_sink``)."""
  cfg: TransformerConfig
  mesh: Optional[Any] = None
  window: Optional[int] = None
  rope: bool = True
  kv_heads: int = 0
  theta: float = 0.0
  sink: bool = False

  @nn.compact
  def __call__(self, x, positions, decode: bool = False, loop_pass: int = 0):
    """``x`` arrives normalized. ``loop_pass`` is which of
    ``cfg.loop_passes`` this call is: the decode cache it owns."""
    cfg = self.cfg
    win = cfg.attention_window if self.window is None else self.window
    dense = lambda feats, logical, name: nn.DenseGeneral(  # noqa: E731
        feats, axis=-1, dtype=cfg.dtype, use_bias=False, name=name,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), logical))
    heads_axis = lambda n: _heads_logical(n, self.mesh)  # noqa: E731

    q = dense((cfg.num_heads, cfg.head_dim),
              ("embed", heads_axis(cfg.num_heads), "kv"), "q")(x)
    # GQA: K/V carry only kv_heads heads (= num_heads unless configured)
    hk = self.kv_heads or cfg.kv_heads
    k = dense((hk, cfg.head_dim), ("embed", heads_axis(hk), "kv"), "k")(x)
    v = dense((hk, cfg.v_head_dim), ("embed", heads_axis(hk), "kv"), "v")(x)
    if cfg.attn_value_scale != 1.0:
      v = (v.astype(jnp.float32) * cfg.attn_value_scale).astype(v.dtype)
    sink = self.param("sink", nn.initializers.zeros, (cfg.num_heads,),
                      jnp.float32) if self.sink else None
    #: heads the one-width kernels and shardings cannot take
    wide = sink is not None or v.shape[-1] != q.shape[-1]
    if wide and self.mesh is not None and self.mesh.size > 1:
      raise ValueError(heads_refusal(
          cfg, "a mesh of %d devices" % self.mesh.size, "mesh"))
    theta = self.theta or cfg.rope_theta

    if cfg.qk_norm:
      q, k = (nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32, name=name)(
          t).astype(t.dtype) for name, t in (("q_norm", q), ("k_norm", k)))
    gate = None
    if cfg.attn_gate:
      gate = dense((cfg.num_heads, cfg.v_head_dim),
                   ("embed", heads_axis(cfg.num_heads), "kv"), "gate")(x)

    index = None
    if cfg.sparse_topk:
      if self.mesh is not None and self.mesh.size > 1:
        raise ValueError(sparse_refusal(
            cfg, "a mesh of %d devices" % self.mesh.size, "mesh"))
      index = self._index(x)

    if decode:
      return self._decode_attend(q, k, v, loop_pass, win, gate, theta, sink,
                                 index)

    if self.rope:
      q = _rotary(q, positions, theta, cfg.rope_dim)
      k = _rotary(k, positions, theta, cfg.rope_dim)

    interp = ops.pallas_interpret()           # forced-flash CI runs
    if index is not None:
      # the whole sequence at once (no cache): every query's candidates are
      # the positions up to its own
      with jax.named_scope("indexer"):
        iq, ik, iw = self._index_rotate(index, positions, theta)
        keep = select_topk(
            index_scores(iq, iw, ik.astype(cfg.dtype)),
            jnp.broadcast_to(jnp.arange(q.shape[1]), q.shape[:2]),
            cfg.sparse_topk)
      if _flash_eligible(cfg, q.shape[1]):
        out = ops.flash_attention(q, k, v, causal=True, interpret=interp,
                                  keep=keep)
      else:
        out = _full_attention_keep(q, _expand_kv(k, cfg.num_heads),
                                   _expand_kv(v, cfg.num_heads), keep)
    elif cfg.use_ring_attention and self.mesh is not None:
      # the ring takes GROUPED K/V as-is: unexpanded blocks rotate on the
      # ICI (num_heads/kv_heads less traffic); the flash kernels consume
      # them unexpanded and the dense block math fuses the expand
      seq_shards = self.mesh.shape.get(mesh_lib.AXIS_SEQUENCE, 1)
      local_seq = q.shape[1] // max(1, seq_shards)
      out = ra.ring_attention(q, k, v, self.mesh, causal=True,
                              use_flash=_flash_eligible(cfg, local_seq),
                              interpret=interp, window=win or None)
    else:
      # heads of two widths have the flash FORWARD only (its backward
      # refuses them by name, ops/flash_attention.py): "auto" keeps such
      # layers, and those with a sink, on the dense path, which trains; a
      # forced "flash" takes the forward
      if _flash_eligible(cfg, q.shape[1]) \
          and not (wide and cfg.attention_impl == "auto"):
        # the flash kernels consume grouped KV natively (grouped-aware
        # BlockSpec; cross-head dK/dV accumulation in the backward grid).
        # Under a >1-device mesh the kernel maps per shard: the TPU
        # compiler refuses to partition a Mosaic kernel on its own
        if sink is not None:
          out = _flash_attention_sink(q, k, v, win, sink, interp)
        elif self.mesh is None or self.mesh.size == 1:
          out = ops.flash_attention(q, k, v, causal=True, interpret=interp,
                                    window=win or None)
        else:
          out = ops.flash_attention_sharded(
              q, k, v, self.mesh, causal=True, interpret=interp,
              window=win or None)
      elif sink is not None:
        out = _full_attention_sink(q, _expand_kv(k, cfg.num_heads),
                                   _expand_kv(v, cfg.num_heads), win, sink)
      else:
        # the dense reference attends at full head count: broadcast each
        # KV head to its query group (XLA fuses the repeat)
        out = ra.full_attention(q, _expand_kv(k, cfg.num_heads),
                                _expand_kv(v, cfg.num_heads), causal=True,
                                window=win or None)

    return self._out_proj(out, gate)

  def _index(self, x):
    """The indexer's projections of the layer's normed input ``x``
    (``TransformerConfig.sparse_topk``): ``(queries [b, s, index_heads,
    index_head_dim], the one key a token [b, s, index_head_dim] after its
    LayerNorm (float32), the heads' weights [b, s, index_heads] float32,
    scaled)``, none rotated yet."""
    cfg = self.cfg
    proj = lambda feats, name: nn.DenseGeneral(  # noqa: E731
        feats, axis=-1, dtype=cfg.dtype, use_bias=False, name=name,
        kernel_init=nn.initializers.lecun_normal())(x)
    iq = proj((cfg.index_heads, cfg.index_head_dim), "index_q")
    ik = nn.LayerNorm(epsilon=cfg.norm_eps, dtype=jnp.float32,
                      name="index_k_norm")(proj(cfg.index_head_dim, "index_k"))
    iw = proj(cfg.index_heads, "index_w").astype(jnp.float32) * (
        cfg.index_heads ** -0.5 * cfg.index_head_dim ** -0.5)
    return iq, ik, iw

  @staticmethod
  def _index_rotate(index, positions, theta):
    """The indexer's queries and key rotated at ``positions`` over ALL their
    dims (half-split, ``theta``'s frequencies of an ``index_head_dim``-wide
    head); the key stays float32 until it is stored."""
    iq, ik, iw = index
    return (_rotary(iq, positions, theta),
            _rotary(ik[:, :, None, :], positions, theta)[:, :, 0], iw)

  def _out_proj(self, out, gate=None):
    cfg = self.cfg
    if gate is not None:
      out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)
    return nn.DenseGeneral(
        cfg.d_model, axis=(-2, -1), dtype=cfg.dtype, use_bias=False,
        name="out",
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), ("heads", "kv", "embed")))(out)

  def _decode_attend(self, q, k, v, loop_pass: int = 0, win: int = 0,
                     gate=None, theta: float = 10000.0, sink=None,
                     index=None):
    """Incremental attention against a KV cache (serving path); ``win`` the
    layer's window, ``gate`` its output gate's pre-activation, ``theta`` its
    rotary base, ``sink`` its softmax's sink (``[num_heads]`` or None). The K
    leaf is as wide as the layer's KV heads times the KEYS' head width, the
    V leaf times the VALUES' (``q``, ``k`` and ``v`` say which).

    Writes the new keys/values at the cache cursor, attends the query
    block against everything cached before it plus the block itself
    (``_cached_attention``: the read takes the cache as it was, so it does
    not wait on the write), and advances the cursor. Cache shape is
    [batch, max_seq_len, kv_heads * head_dim] per layer: heads folded into
    the minor axis, so the stored layout is lane-dense at every head_dim
    and is the layout decode attention computes on — under GQA the cache
    holds only the grouped KV heads (the serving memory win), never an
    expanded copy. A fresh-cache prefill of a block-divisible segment runs
    through the GQA flash kernel instead of the seg × max_seq dense einsum
    (see the cond below).

    The cursor (``cache/index``) is either a SCALAR — all rows in
    lockstep, the classic batched-decode path — or a VECTOR of per-row
    cursors (``serving/``'s slot slabs): each row writes at its own
    offset (a vmapped update-slice, i.e. one scatter) and masks against
    its own length, so one jitted step can advance in-flight requests
    that are at different positions in their sequences.

    A looped model (``cfg.loop_passes`` > 1) calls this once a pass on the
    ONE module: pass ``loop_pass`` owns the leaves ``cached_k_p<u>`` /
    ``cached_v_p<u>`` (scales likewise), every pass reads the one cursor
    where the token began, and the last pass advances it.

    Under ``cfg.kv_ring`` a layer with a window keeps a RING of
    ``cfg.ring_rows(win)`` rows in place of ``max_seq_len``: position ``p``
    in row ``p % rows``, one token a step under per-slot cursors (the
    serving slab; ``SlotDecoder.insert`` turns a prefilled row into one).
    The cursor stays the token's true position.

    A later chunk of a LONG row (``idx > 0``, a row of several
    ``_ROW_BLOCK``s) attends its cache through the flash kernel in blocks of
    rows, from the window's lower edge to its own last row: the dense
    branch's float32 scores of ``seg x heads x max_seq_len`` are 1.6 GB at
    512 x 48 x 16384.

    ``index`` (``TransformerConfig.sparse_topk``; :meth:`_index`'s triple): the
    layer SELECTS. Its rotated index key is written at the cursor into a
    third leaf, ``cached_ik`` ``[batch, max_seq_len, INDEX_LANES]``; every
    query's index scores over its candidates (the cache's rows below the
    cursor and the block's own up to itself) give the mask by query
    (:func:`select_topk`) that each lowering of the attention takes beside
    its positional one: the dense branch, ``ops.decode_attention`` (a row a
    slot), the flash forward of a fresh chunk and the blocked merge of a later
    one (an operand tiled as they walk). A decode step reads the index leaf
    AS IT WAS plus the step's own key (the read does not wait on the write); a
    chunk under a shared cursor reads its row as written, in blocks up to its
    own last position. A chunk that ends at or below ``sparse_topk`` positions
    selects nothing and runs the program it ran without an indexer (decided
    on the traced cursor). Counter ``counters/sparse_kept`` ``[batch]``: the
    entries a decode step's query kept."""
    cfg = self.cfg
    if cfg.kv_page_size > 0:
      return self._decode_attend_paged(q, k, v, win, gate)
    b, seg, h, d = q.shape
    hk, dv = v.shape[2:]
    quant = cfg.kv_cache_dtype == "int8"
    cache_dt = jnp.int8 if quant else cfg.dtype
    sfx = "_p%d" % loop_pass if cfg.loop_passes > 1 else ""
    ring = cfg.ring_rows(win) if cfg.kv_ring else 0
    rows = ring or cfg.max_seq_len
    cached_k = self.variable(
        "cache", "cached_k" + sfx, jnp.zeros, (b, rows, hk * d), cache_dt)
    cached_v = self.variable(
        "cache", "cached_v" + sfx, jnp.zeros, (b, rows, hk * dv), cache_dt)
    if quant:
      k_scale = self.variable("cache", "k_scale" + sfx, jnp.zeros,
                              (b, cfg.max_seq_len, hk), jnp.float32)
      v_scale = self.variable("cache", "v_scale" + sfx, jnp.zeros,
                              (b, cfg.max_seq_len, hk), jnp.float32)
    # a slab with rings is slot-shaped by construction, as a paged one: its
    # cursors are born a vector
    cursor = self.variable(
        "cache", "index", jnp.zeros, (b,) if cfg.kv_ring else (), jnp.int32)
    idx = cursor.value
    vec = idx.ndim == 1          # per-slot cursors (serving slab decode)
    if ring and not (vec and seg == 1):
      raise ValueError(
          "a ring of %d rows (kv_ring, window %d) takes one token a step "
          "under per-slot cursors, got %d tokens under a %s cursor"
          % (ring, win, seg, "per-slot" if vec else "shared"))

    if vec:
      positions = idx[:, None] + jnp.arange(seg)[None, :]
    else:
      positions = idx + jnp.broadcast_to(jnp.arange(seg), (b, seg))
    if self.rope:
      q = _rotary(q, positions, theta, cfg.rope_dim)
      k = _rotary(k, positions, theta, cfg.rope_dim)
    at = idx % ring if ring else idx       # the row the token's K/V take

    # tensor-parallel serving: keep the cache sharded on its folded
    # (grouped) heads axis so each chip holds 1/t of the KV bytes — whole
    # heads, by the divisibility rule the projection kernels share
    # (_heads_logical) — without the constraint GSPMD may gather the cache.
    kv_spec = ("batch", None, _heads_logical(hk, self.mesh))

    def _quantize(x):
      # per-token/head symmetric int8 over the head dim
      xf = x.astype(jnp.float32)
      amax = jnp.max(jnp.abs(xf), axis=-1)               # [b, seg, hk]
      s = jnp.maximum(amax, 1e-8) / 127.0
      v8 = jnp.clip(jnp.round(xf / s[..., None]), -127, 127)
      return v8.astype(jnp.int8), s

    # what the dense attention reads: the cache before this block's write
    # (_cached_attention says why) and the block as the cache stores it
    was = dict(cached_k=cached_k.value, cached_v=cached_v.value)
    if quant:
      k_store, ks = _quantize(k)
      v_store, vs = _quantize(v)
      k_own = k_store.astype(jnp.float32) * ks[..., None]
      v_own = v_store.astype(jnp.float32) * vs[..., None]
      was.update(k_scale=k_scale.value, v_scale=v_scale.value)
      k_scale.value = _constrain(
          _cache_write(k_scale.value, ks, idx, positions, self.mesh),
          kv_spec, self.mesh)
      v_scale.value = _constrain(
          _cache_write(v_scale.value, vs, idx, positions, self.mesh),
          kv_spec, self.mesh)
    else:
      k_store, v_store = k.astype(cfg.dtype), v.astype(cfg.dtype)
      k_own, v_own = k_store, v_store
    cached_k.value = _constrain(
        _cache_write(cached_k.value, k_store.reshape(b, seg, hk * d), at,
                     positions, self.mesh), kv_spec, self.mesh)
    cached_v.value = _constrain(
        _cache_write(cached_v.value, v_store.reshape(b, seg, hk * dv), at,
                     positions, self.mesh), kv_spec, self.mesh)
    if loop_pass == cfg.loop_passes - 1:
      cursor.value = idx + seg
    if index is None:
      return self._out_proj(self._attend_cached(
          q, k, v, k_own, v_own, was, cached_k, cached_v, idx, positions,
          win, ring, sink, quant), gate)

    if vec and seg > 1:
      raise ValueError(sparse_refusal(
          cfg, "a block of %d tokens a lane under per-slot cursors (a "
          "speculative verify window)" % seg, "draft"))
    iq, ik, iw = self._index_rotate(index, positions, theta)
    ik = jnp.pad(ik.astype(cfg.dtype),
                 ((0, 0), (0, 0), (0, INDEX_LANES - ik.shape[-1])))
    cached_ik = self.variable("cache", "cached_ik", jnp.zeros,
                              (b, rows, INDEX_LANES), cfg.dtype)
    ik_was = cached_ik.value
    cached_ik.value = _cache_write(ik_was, ik, at, positions, self.mesh)
    col = jnp.arange(rows)
    attend = lambda keep: self._attend_cached(  # noqa: E731
        q, k, v, k_own, v_own, was, cached_k, cached_v, idx, positions, win,
        ring, sink, quant, keep)

    def _keep_step():
      # per-slot cursors, one token: the leaf as it was and the own key
      with jax.named_scope("indexer"):
        scores = jnp.where(col == idx[:, None],
                           index_scores(iq, iw, ik)[:, 0],      # [b, 1]
                           index_scores(iq, iw, ik_was)[:, 0])  # [b, max]
        keep = select_topk(scores, idx, cfg.sparse_topk)
        own = jnp.take_along_axis(
            keep, jnp.minimum(idx, rows - 1)[:, None], axis=1)
      self.sow("counters", "sparse_kept",
               jnp.sum(keep, axis=-1, dtype=jnp.int32))
      return keep[:, None, :], own[:, :, None]

    def _keep_row():
      # a shared cursor: the row as written holds the block's own keys too
      with jax.named_scope("indexer"):
        keep = select_topk(
            index_scores(iq, iw, cached_ik.value, live=idx + seg),
            positions, cfg.sparse_topk)                     # [b, seg, max]
      return keep, lax.dynamic_slice_in_dim(keep, idx, seg, axis=2)

    if vec:
      out = attend(_keep_step())
    elif seg > cfg.sparse_topk:
      out = attend(_keep_row())
    else:
      out = lax.cond(idx + seg > cfg.sparse_topk,
                     lambda: attend(_keep_row()), lambda: attend(None))
    return self._out_proj(out, gate)

  def _attend_cached(self, q, k, v, k_own, v_own, was, cached_k, cached_v,
                     idx, positions, win, ring, sink, quant, keep=None):
    """The attention of :meth:`_decode_attend` once the block is written:
    ``q``/``k``/``v`` rotated, ``k_own``/``v_own`` the block as the cache
    stores it, ``was`` the leaves before the write, ``cached_k``/``cached_v``
    the variables after it; ``keep`` a selection's ``(mask over the row's
    positions [b, seg, max], mask over the block's own [b, seg, seg])`` or
    None."""
    cfg = self.cfg
    b, seg, h, d = q.shape
    hk, dv = v.shape[2:]
    vec = idx.ndim == 1

    def _dense_attend(_):
      # the cache as it was plus the block itself (what the write above
      # stored of it); per-row cursors (vec) mask each slot against ITS
      # length
      return _cached_attention(
          q, k_own, v_own, q_pos=positions if vec else positions[:1],
          window=win, lengths=idx if vec else None, mesh=self.mesh,
          ring=bool(ring), sink=sink, keep=keep, **was)

    # PREFILL fast path: a fresh-cache multi-token segment attends only
    # within itself (causal), so the flash kernel runs it O(seg²)-tiled
    # over the grouped K/V directly — the dense path does seg × max_seq
    # work against a mostly-empty cache and materializes f32 scores. The
    # cursor check is traced, so chunked prefill (idx > 0, where queries
    # must also see earlier cache entries) falls through to the dense
    # branch of the SAME cond and stays correct.
    # Under a >1-device mesh the kernel needs a shard_map wrap — GSPMD
    # refuses to auto-partition Mosaic kernels — with query and KV heads
    # sharded CONSISTENTLY (both over tensor, or both replicated):
    # mismatched head layouts would break the kernel's local i//g
    # query→KV-head mapping, so such configs prefill through the dense
    # einsums instead.
    single = self.mesh is None or self.mesh.size == 1
    heads_consistent = single or (
        _heads_logical(h, self.mesh) == _heads_logical(hk, self.mesh))
    use_flash_prefill = False
    if not vec and heads_consistent and seg > 1 \
        and cfg.attention_impl != "dense":
      ecfg = cfg
      if cfg.attention_impl == "flash" and not _flash_tiles(seg):
        # serving accepts arbitrary prompt lengths the caller doesn't
        # block-align; degrade forced-flash to "auto" for this internal
        # shape rather than raise (the _generate_fn precedent)
        ecfg = dataclasses.replace(cfg, attention_impl="auto")
      use_flash_prefill = _flash_eligible(ecfg, seg)
    if use_flash_prefill:
      from tensorflowonspark_tpu.ops import flash_attention

      interp = ops.pallas_interpret()

      def _flash_prefill(_):
        if sink is not None:
          return _flash_attention_sink(q, k, v, win, sink, interp)
        if single:
          return flash_attention(
              q, k, v, causal=True, interpret=interp, window=win or None,
              keep=None if keep is None else keep[1]).astype(q.dtype)
        # heads_consistent (above) is what flash_attention_sharded's
        # own both-divide rule needs to shard heads here
        return ops.flash_attention_sharded(
            q, k, v, self.mesh, causal=True, interpret=interp,
            window=win or None).astype(q.dtype)

      def _blocked_attend(_):
        # the row AS WRITTEN above holds this chunk too, so one causal mask
        # over absolute positions covers cache and chunk; a block wholly
        # behind the window or past the chunk is never touched
        from tensorflowonspark_tpu.ops.flash_attention import (
            NEG_INF, flash_attention_block, merge_partials)
        first = jnp.maximum(idx - (win - 1), 0) // _ROW_BLOCK if win else 0

        def one_block(j, partial):
          base = j * _ROW_BLOCK
          kj, vj = (lax.dynamic_slice_in_dim(
              c.value, base, _ROW_BLOCK, axis=1).reshape(b, _ROW_BLOCK, hk, w)
                    for c, w in ((cached_k, d), (cached_v, dv)))
          kept = None if keep is None else lax.dynamic_slice_in_dim(
              keep[0], base, _ROW_BLOCK, axis=2)
          return merge_partials(*partial, *flash_attention_block(
              q, kj, vj, idx, base, causal=True, interpret=interp,
              window=win or None, keep=kept))

        out, lse = lax.fori_loop(
            first, (idx + seg - 1) // _ROW_BLOCK + 1, one_block,
            (jnp.zeros((b, seg, h, dv), jnp.float32),
             jnp.full((b, h, seg), NEG_INF, jnp.float32)))
        if sink is not None:     # once, after the last merge
          out = _sink_rescale(out, lse, sink)
        return out.astype(q.dtype)

      long_row = (single and not quant and cfg.max_seq_len > _ROW_BLOCK
                  and cfg.max_seq_len % _ROW_BLOCK == 0)
      return lax.cond(idx == 0, _flash_prefill,
                      _blocked_attend if long_row else _dense_attend, None)
    return _dense_attend(None)

  def _decode_attend_paged(self, q, k, v, win: int = 0, gate=None):
    """Incremental attention against a PAGED KV cache (serving slabs).

    Per layer the cache is a page POOL — ``pages_k``/``pages_v``
    ``[kv_num_pages, kv_page_size, kv_heads, head_dim]`` — addressed
    through a per-slot ``page_table [batch, kv_pages_per_slot] int32``
    and the VECTOR cursor ``index [batch]``: slot ``b``'s token at
    position ``p`` lives in page ``page_table[b, p // page_size]`` at
    offset ``p % page_size``. Writes are one scatter over the flattened
    (page, offset) indices; reads gather each slot's page list back into
    a ``[batch, pages_per_slot·page_size, ...]`` view and run the same
    masked dense attention as the vector-cursor contiguous branch.

    Page 0 is the TRASH page: unused table entries point at it, so a
    frozen lane (cursor 0, table all-zero) scatters its garbage there
    and positions past ``pages_per_slot`` pages clip onto it — nothing
    a live slot attends is ever touched, because the mask admits only
    ``k_pos <= q_pos`` and every position a live slot can reach lies in
    its own (or its shared read-only prefix) pages.
    """
    cfg = self.cfg
    b, seg, h, d = q.shape
    hk = cfg.kv_heads
    ps, pp = cfg.kv_page_size, cfg.kv_pages_per_slot
    span = pp * ps                       # a slot's maximum visible tokens
    pages_k = self.variable(
        "cache", "pages_k", jnp.zeros, (cfg.kv_num_pages, ps, hk, d),
        cfg.dtype)
    pages_v = self.variable(
        "cache", "pages_v", jnp.zeros, (cfg.kv_num_pages, ps, hk, d),
        cfg.dtype)
    table = self.variable("cache", "page_table", jnp.zeros, (b, pp),
                          jnp.int32)
    # paged decode is slot-shaped by construction: the cursor is born a
    # vector (the contiguous branch's scalar/vector duality doesn't apply)
    cursor = self.variable("cache", "index", jnp.zeros, (b,), jnp.int32)
    idx = cursor.value

    positions = idx[:, None] + jnp.arange(seg)[None, :]        # [b, seg]
    if self.rope:
      q = _rotary(q, positions, cfg.rope_theta)
      k = _rotary(k, positions, cfg.rope_theta)

    # write: token position -> (page, offset) through the table. A
    # position inside the span but past the slot's allocation resolves
    # through an unused table entry to the trash page; a position PAST
    # the span (a speculative verify window overshooting a full slot)
    # is forced to trash explicitly — the clip would otherwise alias it
    # into the slot's LAST page over live attended tokens
    page_slot = jnp.clip(positions // ps, 0, pp - 1)           # [b, seg]
    page_ids = jnp.take_along_axis(table.value, page_slot, axis=1)
    page_ids = jnp.where(positions < pp * ps, page_ids, 0)
    offs = positions % ps
    flat_pages = page_ids.reshape(-1)
    flat_offs = offs.reshape(-1)
    # tensor-parallel serving: keep the page pools sharded on the
    # (grouped) heads dim — the same constraint (and rationale) as the
    # contiguous branch: without it GSPMD may gather the pool, the
    # largest HBM object in serving, every step
    pool_spec = (None, None, _heads_logical(hk, self.mesh), "kv")
    pages_k.value = _constrain(
        pages_k.value.at[flat_pages, flat_offs].set(
            k.astype(cfg.dtype).reshape(b * seg, hk, d)),
        pool_spec, self.mesh)
    pages_v.value = _constrain(
        pages_v.value.at[flat_pages, flat_offs].set(
            v.astype(cfg.dtype).reshape(b * seg, hk, d)),
        pool_spec, self.mesh)
    cursor.value = idx + seg

    # read: gather each slot's pages into its contiguous token view
    kf = pages_k.value[table.value].reshape(b, span, hk, d) \
        .astype(jnp.float32)
    vf = pages_v.value[table.value].reshape(b, span, hk, d) \
        .astype(jnp.float32)
    scale = 1.0 / (d ** 0.5)
    qg = q.reshape(b, seg, hk, h // hk, d).astype(jnp.float32)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kf) * scale
    q_pos = idx[:, None, None] + jnp.arange(seg)[None, :, None]
    k_pos = jnp.arange(span)[None, None, :]
    keep = k_pos <= q_pos                                  # [b, seg, span]
    if win:
      keep = jnp.logical_and(keep, k_pos > q_pos - win)
    scores = jnp.where(keep[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", probs, vf)
    return self._out_proj(o.reshape(b, seg, h, d).astype(q.dtype), gate)


def _swiglu(x, d_ff: int, cfg):
  """``down(silu(gate x) * up x)`` inside the calling module's scope
  (params ``gate``/``up``/``down``): the gated SiLU MLP, dense or as a
  shared expert."""
  h = nn.silu(Proj(cfg, (d_ff,), name="gate")(x)) \
      * Proj(cfg, (d_ff,), name="up")(x)
  return Proj(cfg, (cfg.d_model,), name="down")(h)


class MLPBlock(nn.Module):
  cfg: TransformerConfig
  mesh: Optional[Any] = None

  @nn.compact
  def __call__(self, x):
    cfg = self.cfg
    if cfg.mlp_act == "swiglu":
      return _swiglu(x, cfg.d_ff, cfg)
    h = nn.Dense(cfg.d_ff, dtype=cfg.dtype, use_bias=False, name="up",
                 kernel_init=nn.with_logical_partitioning(
                     nn.initializers.lecun_normal(), ("embed", "mlp")))(x)
    h = nn.gelu(h)
    return nn.Dense(cfg.d_model, dtype=cfg.dtype, use_bias=False,
                    name="down",
                    kernel_init=nn.with_logical_partitioning(
                        nn.initializers.lecun_normal(), ("mlp", "embed")))(h)


class MoEBlock(nn.Module):
  """Expert-routed FFN (see parallel.expert_parallel): dense masked
  dispatch over the ``expert`` mesh axis, top-k routing, with the
  load-balancing auxiliary loss sown under ``intermediates/moe_aux``.

  Constraint: tokens must not be sequence-sharded (MoE layers flatten
  [B, S, D] to tokens, which composes with data/expert sharding only).
  """
  cfg: TransformerConfig
  mesh: Optional[Any] = None

  @nn.compact
  def __call__(self, x):
    from tensorflowonspark_tpu.parallel import expert_parallel as ep

    cfg = self.cfg
    d = x.shape[-1]
    params = {
        "w_gate": self.param(
            "w_gate", nn.initializers.lecun_normal(),
            (d, cfg.moe_experts), jnp.float32),
        "w_up": self.param(
            "w_up", nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("expert", "embed", "mlp")),
            (cfg.moe_experts, d, cfg.d_ff), jnp.float32),
        "w_down": self.param(
            "w_down", nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("expert", "mlp", "embed")),
            (cfg.moe_experts, cfg.d_ff, d), jnp.float32),
    }
    flat = x.reshape(-1, d)
    # one router forward feeds both the dispatch and the aux loss
    dispatch, combine, probs = ep.route(params, flat, cfg.moe_top_k)
    expert_sharded = self.mesh is not None and \
        self.mesh.shape.get(mesh_lib.AXIS_EXPERT, 1) > 1
    if expert_sharded and cfg.moe_capacity_factor > 0:
      # communication-optimal path: tokens exchanged over the expert axis
      # with two all-to-alls, each device runs only its experts (the
      # router re-runs per-shard inside the body — a tiny matmul)
      y = ep.moe_ffn_a2a(params, flat, self.mesh,
                         capacity_factor=cfg.moe_capacity_factor,
                         top_k=cfg.moe_top_k)
    elif expert_sharded:
      y = ep.moe_ffn(params, flat, self.mesh, top_k=cfg.moe_top_k,
                     routing=(dispatch, combine))
    else:
      y = ep.moe_ffn_reference(params, flat, top_k=cfg.moe_top_k,
                               routing=(dispatch, combine))
    self.sow("intermediates", "moe_aux",
             ep.aux_loss_from(probs, dispatch, cfg.moe_top_k))
    return y.reshape(x.shape).astype(x.dtype)


def _constrain(x, spec, mesh):
  """Activation sharding constraint with explicit rules + mesh.

  ``nn.with_logical_constraint`` without a rules context (or mesh) is a
  SILENT NO-OP — flax returns ``x`` unchanged. Discovered in round 3: every
  activation constraint in this model was inert, which is why the round-2
  multichip dryrun showed SPMD involuntarily rematerializing the embedding
  activations. Passing ``rules=LOGICAL_RULES, mesh=mesh`` makes the
  constraint real; ``mesh=None`` (single device) stays a no-op by design.
  """
  if mesh is None:
    return x
  from tensorflowonspark_tpu.parallel import sharding as sh
  return nn.with_logical_constraint(x, spec, rules=sh.LOGICAL_RULES,
                                    mesh=mesh)


class Block(nn.Module):
  """One pre-norm residual layer: ``x += Mix(norm(x)); x += FFN(norm(x))``.
  ``mixer``/``ffn`` pick the two (``TransformerConfig.layer_types`` /
  ``ffn_types``); the defaults are the attention + MLP block.
  ``cfg.post_norm`` norms each branch's output too (:meth:`_sandwich`)."""
  cfg: TransformerConfig
  mesh: Optional[Any] = None
  use_moe: bool = False
  mixer: str = "attn"
  ffn: str = "mlp"
  window: Optional[int] = None      # the attention's (Attention.window)
  rope: bool = True
  kv_heads: int = 0                 # Attention.kv_heads / theta / sink
  theta: float = 0.0
  sink: bool = False

  def _attend(self, y, positions, decode, loop_pass: int = 0):
    """This layer's attention over the normed ``y``; a model with per-layer
    windows runs it under ``jax.named_scope`` ``attn_window`` /
    ``attn_full``, one that selects (``sparse_topk``) under ``attn_sparse``
    with its scores and selection under ``indexer`` inside."""
    attn = Attention(self.cfg, self.mesh, self.window, self.rope,
                     self.kv_heads, self.theta, self.sink, name="attn")
    scope = jax.named_scope("attn_window" if self.window else "attn_full") \
        if self.cfg.layer_windows else contextlib.nullcontext()
    if self.cfg.sparse_topk:       # the indexer's own scope lies inside
      scope = jax.named_scope("attn_sparse")
    with scope:
      return attn(y, positions, decode=decode, loop_pass=loop_pass)

  @nn.compact
  def __call__(self, x, positions, decode: bool = False, loop_pass: int = 0,
               n_valid=None):
    cfg = self.cfg
    if self.mixer != "attn" or self.ffn != "mlp":
      return self._typed(x, positions, decode, n_valid)
    if cfg.post_norm:
      return self._sandwich(x, positions, decode, loop_pass)
    y = _make_layer_norm(cfg, self.mesh, "ln1")(x)
    x = x + self._attend(y, positions, decode, loop_pass)
    y = _make_layer_norm(cfg, self.mesh, "ln2")(x)
    if self.use_moe:
      x = x + MoEBlock(cfg, self.mesh, name="moe")(y)
    else:
      x = x + MLPBlock(cfg, self.mesh, name="mlp")(y)
    if decode:
      return x
    return _constrain(x, ("batch", "sequence", "embed"), self.mesh)

  def _sandwich(self, x, positions, decode, loop_pass):
    """The attention + MLP layer with a norm before AND after each branch:
    ``x += ln1_out(Attn(ln1(x))); x += ln2_out(MLP(ln2(x)))``. The norms
    compute in float32; what joins the stream is rounded to its dtype."""
    cfg = self.cfg
    norm = lambda name: _make_layer_norm(cfg, self.mesh, name)  # noqa: E731
    y = self._attend(norm("ln1")(x), positions, decode, loop_pass)
    x = x + norm("ln1_out")(y).astype(x.dtype)
    y = MLPBlock(cfg, self.mesh, name="mlp")(norm("ln2")(x))
    x = x + norm("ln2_out")(y).astype(x.dtype)
    if decode:
      return x
    return _constrain(x, ("batch", "sequence", "embed"), self.mesh)

  def _typed(self, x, positions, decode, n_valid=None):
    """A layer whose mixer or feed-forward is not the default pair. Their
    modules are imported HERE, so a model of plain blocks never loads
    them; each runs under its own ``jax.named_scope``. With
    ``cfg.post_norm`` each branch's output is normed too (``ln1_out`` /
    ``ln2_out``), as in :meth:`_sandwich`. ``n_valid`` (how many of the
    chunk's tokens are real) goes to the one mixer whose cache has no
    position axis for a cursor to mask: "kda"."""
    cfg = self.cfg
    if cfg.act_f32:
      x = x.astype(jnp.float32)    # the residual stream is not rounded
    norm = lambda name: _make_layer_norm(cfg, self.mesh, name)  # noqa: E731
    joins = lambda y, name: x + (                               # noqa: E731
        norm(name)(y).astype(x.dtype) if cfg.post_norm else y)
    y = norm("ln1")(x)
    if self.mixer == "kda":
      from tensorflowonspark_tpu.models import kda
      with jax.named_scope("kda"):
        x = joins(kda.KDA(cfg, name="kda")(y, decode=decode,
                                           n_valid=n_valid), "ln1_out")
    elif self.mixer == "mla":
      from tensorflowonspark_tpu.models import mla
      with jax.named_scope("mla"):
        x = joins(mla.MLA(cfg, self.mesh, name="mla")(y, decode=decode),
                  "ln1_out")
    else:
      x = joins(self._attend(y, positions, decode), "ln1_out")
    y = norm("ln2")(x)
    if self.ffn == "experts":
      from tensorflowonspark_tpu.models import experts
      with jax.named_scope("moe"):
        return joins(experts.HeldExperts(cfg, self.mesh, name="moe")(y),
                     "ln2_out")
    return joins(MLPBlock(cfg, self.mesh, name="mlp")(y), "ln2_out")


def _remat_block(cfg: TransformerConfig):
  """``nn.remat(Block)`` under the configured save policy.

  "none": only block boundaries survive to the backward (everything
  inside recomputes — max memory savings). "dots": MXU (matmul) outputs
  are saved and only elementwise/VPU work recomputes
  (``jax.checkpoint_policies.dots_with_no_batch_dims_saveable``) — on an
  HBM-bound chip this buys most of the batch-size headroom at a fraction
  of the ~21% full-recompute cost, making bigger-batch configs the MFU
  lever they should be.
  """
  if cfg.remat_policy == "dots":
    return nn.remat(
        Block,
        policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
  return nn.remat(Block)


class TiedEmbed(nn.Module):
  """Tied input/output embedding with SPMD-friendly lookup layouts.

  Drop-in for the ``nn.Embed`` it replaces — same param path
  (``params["embed"]["embedding"]``), same ``attend`` contract — but the
  lookup controls its shardings: under a mesh where the table is
  (vocab->tensor, embed->fsdp) and activations are (batch, sequence)-sharded,
  a naive gather leaves SPMD resharding a [B, S, D] tensor it can only
  "involuntarily fully rematerialize" (the round-2 MULTICHIP warning).

  * ``gather``: constrain the lookup table to ("vocab", None) first — one
    explicit all-gather of the small [V, D] table over the embed axis — so
    the gather result is born replicated on D and SPMD's repartition to
    (batch, sequence, embed) is a local slice.
  * ``one_hot``: contract one_hot(tokens) against the still-sharded table;
    the vocab contraction becomes a psum over the tensor axis and the result
    arrives already (batch, sequence)-sharded with D on fsdp. No table
    all-gather at all; costs 2·B·S·V·D FLOPs.
  """
  cfg: TransformerConfig
  mesh: Optional[Any] = None

  def setup(self):
    self.embedding = self.param(
        "embedding",
        nn.with_logical_partitioning(nn.initializers.normal(0.02),
                                     ("vocab", "embed")),
        (self.cfg.vocab_size, self.cfg.d_model), jnp.float32)

  def __call__(self, tokens):
    cfg = self.cfg
    table = jnp.asarray(self.embedding, cfg.dtype)
    if cfg.embed_lookup == "one_hot":
      one_hot = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=cfg.dtype)
      one_hot = _constrain(one_hot, ("batch", "sequence", "vocab"),
                           self.mesh)
      return jnp.einsum("bsv,vd->bsd", one_hot, table)
    table = _constrain(table, ("vocab", None), self.mesh)
    return jnp.take(table, tokens, axis=0)

  def attend(self, x):
    table = jnp.asarray(self.embedding, self.cfg.dtype)
    return jnp.einsum("...d,vd->...v", x, table)


class Transformer(nn.Module):
  """Causal LM. Input: int32 token ids [batch, seq]; output: logits."""
  cfg: TransformerConfig
  mesh: Optional[Any] = None

  @nn.compact
  def __call__(self, tokens, decode: bool = False,
               return_hidden: bool = False,
               exit_layer: Optional[int] = None, logits_at=None,
               n_valid=None):
    """``exit_layer`` (static) runs only the first N blocks before the
    final norm + tied projection — the SHALLOW-EXIT draft of
    self-speculative decoding (serving/slots.py): the draft is a prefix
    of the target's own layers, so it shares params and KV slabs and
    needs no second model. Untouched layers' cache entries pass through
    an ``apply`` unchanged (flax keeps unvisited collection entries), so
    a shallow decode step advances only the visited layers' cursors.

    ``n_valid`` (a traced int32 scalar, ``decode`` only; ``None`` = all):
    how many of ``tokens [batch, seq]`` are real, the rest being a padded
    prefill chunk's padding behind them. Layers whose cache is indexed by
    position need no telling (the caller's cursor masks what the padding
    wrote); a recurrent layer ("kda") leaves its state and convolution
    tail where the last real token left them."""
    cfg = self.cfg
    if exit_layer is not None and not 1 <= exit_layer <= cfg.num_layers:
      raise ValueError("exit_layer must be in [1, num_layers=%d], got %r"
                       % (cfg.num_layers, exit_layer))
    if cfg.loop_passes > 1:
      if exit_layer is not None:
        raise ValueError(loop_refusal(
            cfg, "draft", "speculative decoding's shallow exit (exit_layer=%d)"
            % exit_layer))
      if decode and cfg.loop_exit_threshold < 1.0:
        raise ValueError(loop_refusal(
            cfg, "early_exit",
            "loop_exit_threshold=%r in the cached decode path"
            % cfg.loop_exit_threshold))
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    emb = TiedEmbed(cfg, self.mesh, name="embed")
    x = emb(tokens)
    if cfg.embed_scale != 1.0:
      x = (x.astype(jnp.float32) * cfg.embed_scale).astype(x.dtype)
    if not decode:
      x = _constrain(x, ("batch", "sequence", "embed"), self.mesh)

    block = Block
    if cfg.remat and not decode:
      block = _remat_block(cfg)
    layers = []
    for i in range(cfg.num_layers if exit_layer is None else exit_layer):
      use_moe = (cfg.moe_experts > 0
                 and i % cfg.moe_every == cfg.moe_every - 1)
      layers.append(block(cfg, self.mesh, use_moe,
                          cfg.layer_types[i] if cfg.layer_types else "attn",
                          cfg.ffn_types[i] if cfg.ffn_types else "mlp",
                          cfg.layer_windows[i] if cfg.layer_windows else None,
                          bool(cfg.layer_rope[i]) if cfg.layer_rope else True,
                          cfg.layer_kv_heads[i] if cfg.layer_kv_heads else 0,
                          cfg.layer_rope_theta[i] if cfg.layer_rope_theta
                          else 0.0,
                          bool(cfg.layer_sink[i]) if cfg.layer_sink else False,
                          name="layer_%d" % i))
    ln_f = _make_layer_norm(cfg, self.mesh, "ln_f")
    if cfg.loop_passes == 1:
      for layer in layers:
        x = layer(x, positions, True, 0, n_valid) if decode \
            else layer(x, positions)
      if logits_at is not None:
        # one position (a traced scalar): only that row goes through the
        # final norm and the head, the result is [batch, 1, vocab] — a
        # padded prefill chunk wants the logits of its last REAL token
        x = lax.dynamic_slice_in_dim(x, logits_at, 1, axis=1)
      x = ln_f(x)
    else:
      x = self._loop(x, positions, decode, layers, ln_f, logits_at)
    if return_hidden:
      # pre-projection hidden states for the fused blocked loss
      # (:func:`causal_lm_loss_blocked`) — callers project against the
      # tied table chunk-by-chunk instead of materializing [B, S, V]
      return x.astype(cfg.dtype)
    if not cfg.tie_embeddings:
      return Proj(cfg, (cfg.vocab_size,), name="head")(x).astype(
          jnp.float32)
    # tied output projection (attend to the embedding table)
    logits = emb.attend(x.astype(cfg.dtype))
    return logits.astype(jnp.float32)

  def _loop(self, x, positions, decode, layers, ln_f, logits_at):
    """``cfg.loop_passes`` passes over the one set of ``layers``, the final
    norm closing each pass; returns the normed stream of each token's EXIT
    pass (``exit_pass``: the last at the published threshold 1.0), at
    ``logits_at`` alone where that is given. The exit pass of every token
    is sown under ``counters/exit_pass`` ``[batch, seq]``: where a caller
    collects it the gates are computed, elsewhere the compiler drops them."""
    cfg = self.cfg
    n, gates, normed = cfg.loop_passes, [], []
    gate = nn.Dense(1, dtype=jnp.float32, name="exit_gate")
    for u in range(n):
      with jax.named_scope("pass_%d" % u):
        for layer in layers:
          x = layer(x, positions, True, u) if decode else layer(x, positions)
        y = ln_f(x)                          # float32, [batch, seq, d]
        if u < n - 1:
          gates.append(jax.nn.sigmoid(gate(y)[..., 0]))
          x = y.astype(x.dtype)
        if cfg.loop_exit_threshold < 1.0 or u == n - 1:
          normed.append(y)
    exits = exit_pass(gates, cfg.loop_exit_threshold)        # [batch, seq]
    self.sow("counters", "exit_pass", exits)
    out = normed[-1]
    for u, y in enumerate(normed[:-1]):
      out = jnp.where((exits == u + 1)[..., None], y, out)
    if logits_at is not None:
      out = lax.dynamic_slice_in_dim(out, logits_at, 1, axis=1)
    return out


@functools.lru_cache(maxsize=8)
def _generate_fn(cfg: TransformerConfig, plen: int, num_steps: int):
  """Cached jitted decode loop; params/buf are runtime args so repeated
  generate calls reuse one compilation and params are never baked in as
  compile-time constants."""
  total = plen + num_steps
  if cfg.attention_impl == "flash" and not _flash_tiles(total):
    # the generation buffer's length (plen + num_steps) is an internal
    # shape callers don't control block-alignment of — a forced-flash
    # model must still generate, so degrade to "auto" here (flash when
    # the buffer divides, dense otherwise) rather than raise
    cfg = dataclasses.replace(cfg, attention_impl="auto")
  model = Transformer(cfg)

  def decode(params, buf):
    # recompile sentinel seam (obs/device.py): one trace = one jit-cache
    # entry; steady-state generation must never bump this post-warmup
    obs_device.note_trace("transformer.generate")

    def step(i, buf):
      logits = model.apply({"params": params}, buf)     # [b, total, V]
      pos = plen + i - 1
      last = lax.dynamic_index_in_dim(logits, pos, axis=1, keepdims=False)
      nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)  # [b]
      return lax.dynamic_update_slice(buf, nxt[:, None], (0, plen + i))

    return lax.fori_loop(0, num_steps, step, buf)

  return jax.jit(decode)


def greedy_generate(params, cfg: TransformerConfig, prompt, num_steps: int,
                    mesh=None):
  """Greedy autoregressive decoding (jit-compiled fixed-length loop).

  prompt: int32 [batch, prompt_len]. Returns [batch, prompt_len+num_steps].
  Recomputes the full forward per step — simple and cache-free; use
  :func:`greedy_generate_kv` for the O(1)-per-token serving path. The
  compiled loop is cached per (config, prompt_len, num_steps).
  """
  del mesh  # generation runs wherever params live; sharding via params
  b, plen = prompt.shape
  buf = jnp.zeros((b, plen + num_steps), jnp.int32)
  buf = lax.dynamic_update_slice(buf, prompt.astype(jnp.int32), (0, 0))
  return _generate_fn(cfg, plen, num_steps)(params, buf)


def _select_token(logits, rng, temperature: float, top_k: int):
  """Greedy (temperature == 0) or top-k temperature sampling."""
  if temperature == 0.0:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)
  scaled = logits.astype(jnp.float32) / temperature
  if top_k > 0 and top_k < logits.shape[-1]:
    kth = lax.top_k(scaled, top_k)[0][..., -1:]   # dedicated TPU top-k op
    scaled = jnp.where(scaled < kth, -1e30, scaled)
  return jax.random.categorical(rng, scaled, axis=-1).astype(jnp.int32)


# 32 entries, not 8: serving traffic (and the parity suites) legitimately
# touch dozens of (batch, prompt_len, num_steps) shapes — an 8-entry
# cache thrashes and recompiles shapes it just evicted. Entries hold
# compiled executables (code, not params), so the residency cost is MBs
@functools.lru_cache(maxsize=32)
def _kv_generate_fn(cfg: TransformerConfig, batch: int, plen: int,
                    num_steps: int, temperature: float, top_k: int,
                    mesh=None, eos_id=None, pad_id: int = 0):
  """Cached jitted KV-cache decode: prefill once, then one token per step
  against the per-layer key/value cache — O(1) attention work per new
  token instead of a full-sequence recompute.

  With ``eos_id``, the scan carries a per-sequence done-mask: a row that
  sampled ``eos_id`` keeps its EOS token and emits ``pad_id`` for every
  later step (its unavoidable padding work inside this fixed-shape loop —
  the ``serving/`` slot engine is the path that RECLAIMS those steps by
  freeing the slot). The loop itself stays fixed-length so the compiled
  program's shape never depends on data.

  With ``mesh``, decode is tensor-parallel (the reference's dedicated
  inference layer scaled past one chip, TFModel.scala:245-292): params go
  in under their logical shardings (heads over the tensor axis), the KV
  cache stays heads-sharded on-chip (``_decode_attend``'s constraint), the
  batch dim rides the data axes, and the output gathers replicated. The
  jit carries explicit in/out shardings so host-resident bundle params are
  placed correctly on first call."""
  model = Transformer(cfg, mesh=mesh)

  def decode(params, prompt, rng):
    obs_device.note_trace("transformer.kv_generate")
    variables = {"params": params, "cache": _zero_cache(model, batch)}
    logits, mutated = model.apply(variables, prompt, decode=True,
                                  mutable=["cache"])
    rng, sub = jax.random.split(rng)
    nxt = _select_token(logits[:, -1], sub, temperature, top_k)
    done = (nxt == eos_id) if eos_id is not None \
        else jnp.zeros((batch,), jnp.bool_)

    def step(carry, _):
      cache, tok, rng, done = carry
      logits, mutated = model.apply({"params": params, "cache": cache},
                                    tok[:, None], decode=True,
                                    mutable=["cache"])
      rng, sub = jax.random.split(rng)
      new = _select_token(logits[:, -1], sub, temperature, top_k)
      if eos_id is not None:
        new = jnp.where(done, jnp.int32(pad_id), new)
        done = jnp.logical_or(done, new == eos_id)
      return (mutated["cache"], new, rng, done), new

    # prefill produced g_1; each scan iteration computes one further token
    _, toks = lax.scan(step, (mutated["cache"], nxt, rng, done), None,
                       length=num_steps - 1)
    generated = jnp.concatenate([nxt[:, None], toks.T], axis=1) \
        if num_steps > 1 else nxt[:, None]
    return jnp.concatenate([prompt, generated], axis=1)

  if mesh is None:
    return jax.jit(decode)
  from tensorflowonspark_tpu.parallel import sharding as sh
  abs_boxed = jax.eval_shape(
      lambda: model.init(jax.random.PRNGKey(0),
                         jnp.zeros((batch, 1), jnp.int32),
                         decode=True))["params"]
  param_sharding = sh.param_sharding_from_boxed(abs_boxed, mesh)
  jitted = jax.jit(decode,
                   in_shardings=(param_sharding, sh.batch_sharding(mesh),
                                 sh.replicated(mesh)),
                   out_shardings=sh.replicated(mesh))

  def call(params, prompt, rng):
    # checkpoint-restored params arrive COMMITTED to one device and jit
    # refuses to reshard committed args — device_put places them onto the
    # mesh (a no-op for already-placed arrays, so steady-state serving
    # pays nothing)
    return jitted(jax.device_put(params, param_sharding), prompt, rng)

  call.jitted = jitted   # AOT surface (mosaic_gate lowers this directly)
  return call


def greedy_generate_kv(params, cfg: TransformerConfig, prompt,
                       num_steps: int, temperature: float = 0.0,
                       top_k: int = 0, rng=None, mesh=None,
                       eos_id=None, pad_id: int = 0):
  """Decoding with a per-layer KV cache (the serving path).

  Greedy by default; ``temperature > 0`` samples (optionally top-k
  filtered) using ``rng``. Semantically identical to
  :func:`greedy_generate` when greedy, but each new token attends against
  cached keys/values rather than recomputing the full prefix — requires
  prompt_len + num_steps <= cfg.max_seq_len. With ``mesh``, decode runs
  tensor-parallel: heads (and the heads-sharded KV cache) split over the
  tensor axis, batch over the data axes (see ``_kv_generate_fn``).

  ``eos_id`` enables per-sequence stopping: a row that emits ``eos_id``
  keeps the EOS token and every later position is ``pad_id`` (the output
  shape stays [b, plen + num_steps]); tokens before the stop are
  identical to the eos-free decode. The loop still runs ``num_steps``
  device steps — reclaiming finished rows' steps is what
  ``serving.ServingEngine`` (continuous batching) is for.
  """
  b, plen = prompt.shape
  if plen + num_steps > cfg.max_seq_len:
    raise ValueError(
        "generation of %d tokens from a %d-token prompt exceeds the "
        "cfg.max_seq_len=%d cache" % (num_steps, plen, cfg.max_seq_len))
  if temperature < 0:
    raise ValueError("temperature must be >= 0, got %r" % temperature)
  if eos_id is not None and int(eos_id) == int(pad_id):
    raise ValueError("eos_id and pad_id must differ (both %d): a padded "
                     "position would read as a fresh stop" % int(pad_id))
  if rng is None:
    if temperature != 0:
      # a silent fixed key would make every "sampled" call identical
      raise ValueError("temperature > 0 requires an explicit rng key")
    rng = jax.random.PRNGKey(0)
  pad = 0
  if mesh is not None:
    # the batch dim shards over the data axes; a ragged final serving
    # batch (pipeline.yield_batch's `if count > 0` tail) is padded up to
    # the axis extent and sliced back after — decode rows are independent,
    # so padding never changes real rows' greedy tokens (with
    # temperature > 0 the padded shape shifts the vectorized draw, which
    # sampling semantics permit)
    from tensorflowonspark_tpu.parallel import mesh as mesh_lib
    pad = (-b) % mesh_lib.axis_size(mesh, mesh_lib.AXIS_DATA,
                                    mesh_lib.AXIS_FSDP)
  if pad:
    prompt = jnp.concatenate(
        [prompt.astype(jnp.int32),
         jnp.zeros((pad, plen), jnp.int32)], axis=0)
  out = _kv_generate_fn(cfg, b + pad, plen, num_steps, float(temperature),
                        int(top_k), mesh,
                        None if eos_id is None else int(eos_id),
                        int(pad_id))(params, prompt.astype(jnp.int32), rng)
  return out[:b] if pad else out


def _zero_cache(model, batch: int):
  """A fresh all-zeros decode cache for ``model``: the cache's shapes from
  an ABSTRACT init of the decode path on a dummy token, then zeros. Run
  for real, ``init`` draws every parameter and executes every layer op by
  op, each op its own small program, only for the result to be thrown
  away: seconds of every serving start at gpt2-large's size."""
  shapes = jax.eval_shape(
      lambda: model.init(jax.random.PRNGKey(0),
                         jnp.zeros((batch, 1), jnp.int32),
                         decode=True)["cache"])
  return jax.tree.map(lambda leaf: jnp.zeros(leaf.shape, leaf.dtype), shapes)


def _set_cache_cursor(cache, value):
  """Rewind every layer's decode cursor (the ``index`` cache leaves).

  Speculative rollback needs nothing else: entries past the cursor are
  never attended (the causal+unwritten mask) and the next write
  overwrites them, so rejected drafts cost a cursor assignment, not a
  cache restore."""
  from jax.tree_util import tree_map_with_path

  def f(path, leaf):
    if path and getattr(path[-1], "key", None) == "index":
      return jnp.asarray(value, leaf.dtype)
    return leaf

  return tree_map_with_path(f, cache)


@functools.lru_cache(maxsize=4)
def _spec_generate_fn(draft_cfg: TransformerConfig, cfg: TransformerConfig,
                      batch: int, plen: int, num_steps: int, k: int,
                      mesh=None):
  """Cached jitted greedy speculative decode (see
  :func:`speculative_generate_kv`). ``mesh`` (single-device) only binds
  the jit to a device for AOT lowering — the deviceless gate's surface."""
  draft = Transformer(draft_cfg)
  target = Transformer(cfg)

  def decode(draft_params, params, prompt):
    cache_d = _zero_cache(draft, batch)
    cache_t = _zero_cache(target, batch)
    # prefill both; the TARGET's argmax after the prompt is token 1
    logits_t, mut_t = target.apply({"params": params, "cache": cache_t},
                                   prompt, decode=True, mutable=["cache"])
    _, mut_d = draft.apply({"params": draft_params, "cache": cache_d},
                           prompt, decode=True, mutable=["cache"])
    cache_t, cache_d = mut_t["cache"], mut_d["cache"]
    g1 = jnp.argmax(logits_t[:, -1], -1).astype(jnp.int32)

    total = plen + num_steps + k + 1   # slack: a round may overshoot
    buf = jnp.zeros((batch, total), jnp.int32)
    buf = lax.dynamic_update_slice(buf, prompt.astype(jnp.int32), (0, 0))
    buf = lax.dynamic_update_slice(buf, g1[:, None], (0, plen))

    def cond(carry):
      return carry[1] < num_steps

    def body(carry):
      buf, n_gen, last, cache_t, cache_d = carry
      # both cursors sit at plen + n_gen - 1 (tokens CONSUMED so far)

      def dscan(c, _):
        cache, tok = c
        lg, mu = draft.apply({"params": draft_params, "cache": cache},
                             tok[:, None], decode=True, mutable=["cache"])
        nxt = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)
        return (mu["cache"], nxt), nxt

      (cache_d, _), P = lax.scan(dscan, (cache_d, last), None, length=k)
      P = P.T                                          # [b, k] proposals

      # ONE target pass scores all k proposals: inputs [last, p1..p_{k-1}],
      # logits[:, j] is the target's prediction AFTER input j
      V = jnp.concatenate([last[:, None], P[:, :k - 1]], axis=1)
      lg_t, mut_t = target.apply({"params": params, "cache": cache_t}, V,
                                 decode=True, mutable=["cache"])
      cache_t = mut_t["cache"]
      T = jnp.argmax(lg_t, -1).astype(jnp.int32)       # [b, k]

      # longest agreeing prefix; min over rows keeps the batch in
      # lockstep (rows that accepted more get exactly those tokens back
      # as the bonus — still the target's greedy output)
      ok = (P == T).astype(jnp.int32)
      m = jnp.min(jnp.sum(jnp.cumprod(ok, axis=1), axis=1))
      bonus = lax.dynamic_index_in_dim(T, jnp.minimum(m, k - 1), 1,
                                       keepdims=True)  # [b, 1]
      emit = jnp.concatenate([P, jnp.zeros((batch, 1), jnp.int32)], axis=1)
      emit = lax.dynamic_update_slice(emit, bonus, (0, jnp.minimum(m, k)))
      buf = lax.dynamic_update_slice(buf, emit, (0, plen + n_gen))

      adv = jnp.where(m < k, m + 1, k)       # accepted + bonus
      new_last = jnp.where(m < k, bonus[:, 0], P[:, k - 1])
      new_cursor = plen + n_gen + adv - 1
      return (buf, n_gen + adv, new_last,
              _set_cache_cursor(cache_t, new_cursor),
              _set_cache_cursor(cache_d, new_cursor))

    buf, _, _, _, _ = lax.while_loop(
        cond, body, (buf, jnp.asarray(1, jnp.int32), g1, cache_t, cache_d))
    return buf[:, :plen + num_steps]

  if mesh is None:
    return jax.jit(decode)
  from tensorflowonspark_tpu.parallel import sharding as sh
  r = sh.replicated(mesh)
  return jax.jit(decode, in_shardings=(r, r, r), out_shardings=r)


def speculative_generate_kv(draft_params, draft_cfg: TransformerConfig,
                            params, cfg: TransformerConfig, prompt,
                            num_steps: int, draft_k: int = 4):
  """Greedy speculative decoding: a cheap DRAFT model proposes
  ``draft_k`` tokens per round and the target verifies them in ONE
  batched decode pass — the target runs ~num_steps/(accepted+1) forward
  passes instead of num_steps, and the output is EXACTLY the target's
  own greedy decode (greedy acceptance is lossless; pinned by test).

  Rollback is free by design: rejected draft entries sit past the
  rewound cache cursor, masked from attention and overwritten by the
  next round (:func:`_set_cache_cursor`). Batched rows accept the
  row-wise MINIMUM prefix each round (lockstep cursors); rows that
  agreed further simply receive those same tokens via the bonus path.

  Both configs must share a vocabulary; requires
  ``prompt_len + num_steps + draft_k <= max_seq_len`` on both models
  (a round's draft writes may transiently overshoot the kept output).
  """
  if draft_cfg.vocab_size != cfg.vocab_size:
    raise ValueError("draft and target must share a vocabulary (%d vs %d)"
                     % (draft_cfg.vocab_size, cfg.vocab_size))
  if draft_k < 1:
    raise ValueError("draft_k must be >= 1, got %d" % draft_k)
  b, plen = prompt.shape
  need = plen + num_steps + draft_k
  for name, c in (("draft", draft_cfg), ("target", cfg)):
    if need > c.max_seq_len:
      raise ValueError(
          "speculative decode needs %d cache slots (prompt %d + steps %d "
          "+ draft_k %d) but the %s max_seq_len is %d"
          % (need, plen, num_steps, draft_k, name, c.max_seq_len))
  return _spec_generate_fn(draft_cfg, cfg, b, plen, num_steps,
                           int(draft_k))(draft_params, params,
                                         prompt.astype(jnp.int32))


# per-process meshes for MeshSpec-carrying serving bundles (see
# make_serving_predict_fn._mesh — deliberately NOT closure state)
_SERVING_MESH_CACHE = {}

# per-process continuous-batching engines for variable-length serving
# batches (same NOT-closure-state rationale: a live ServingEngine holds a
# thread + device arrays and must never ride a pickled bundle)
_SERVING_ENGINE_CACHE = {}

# how long a cached-engine rebuild waits for the old engine to finish its
# accepted requests before stopping it (ServingEngine.drain — a param
# swap must shed zero accepted work; docs/ROBUSTNESS.md)
_SERVING_ENGINE_DRAIN_TIMEOUT = 60.0


def _prompt_rows(prompts):
  """Normalize a predict-fn prompt column to (rows, ragged?).

  ``rows`` is a list of 1-D int32 arrays; ``ragged`` is True when rows
  disagree on length — list/tuple columns of per-row sequences and
  object-dtype arrays (``pipeline``'s ragged-column fallback) both land
  here. Rectangular ndarrays return (None, False): the batched
  fixed-shape path handles them without row materialization.
  """
  import numpy as np
  if isinstance(prompts, np.ndarray) and prompts.dtype != object:
    return None, False
  seq = list(prompts)
  rows = [np.atleast_1d(np.asarray(r, np.int32).ravel()) for r in seq]
  lengths = {len(r) for r in rows}
  return rows, len(lengths) > 1


def make_serving_predict_fn(cfg: TransformerConfig, num_steps: int,
                            temperature: float = 0.0, top_k: int = 0,
                            seed: int = 0, mesh=None, mesh_spec=None,
                            eos_id=None, pad_id: int = 0,
                            num_slots=None):
  """Build a ``predict_fn(params, batch)`` for ``pipeline.export_bundle``.

  The batched KV-cache serving loop as a pipeline bundle: TFModel.transform
  batches rows with ``yield_batch``, and each batch decodes through
  :func:`greedy_generate_kv` (prefill once, then O(1) attention per new
  token). ``batch`` maps an input tensor name to a stacked int32 prompt
  array [B, prompt_len] (prompts in a partition must share a length);
  returns ``{"tokens": [B, prompt_len + num_steps]}``.

  The jitted decode is cached per (config, batch, prompt_len, num_steps),
  so steady-state serving reuses one compilation per shape. With
  ``temperature > 0`` the sampling key is folded with the batch content
  and a per-process call counter, so different batches (and repeated
  serves of the same batch) draw different streams — never the fixed-key
  repetition ``greedy_generate_kv``'s explicit-rng guard exists to
  prevent. ``mesh`` makes each serve tensor-parallel over its axes (the
  multi-chip inference layer, reference TFModel.scala:245-292). A live
  Mesh holds PJRT device objects and cannot ride a pickled bundle — for
  serving through ``pipeline.export_bundle`` / ``TFModel.transform`` pass
  ``mesh_spec`` (a picklable ``parallel.mesh.MeshSpec``) instead: each
  executor process builds the mesh from ITS visible devices on first
  serve (the per-executor-session pattern of the reference's JVM layer).

  VARIABLE-LENGTH batches (a list/object column whose rows disagree on
  prompt length — ``TFModel.transform``'s ragged-column fallback) route
  through the continuous-batching ``serving.ServingEngine`` instead of
  the fixed-shape loop: one persistent per-process engine per config
  (``num_slots`` slots, default ``TOS_SERVE_SLOTS``), EOS early-exit via
  ``eos_id``, outputs right-padded with ``pad_id`` to a rectangle. The
  engine is greedy-only, so ragged batches with ``temperature > 0``
  raise. ``eos_id`` also applies on the rectangular path (per-sequence
  stop inside the fixed loop).
  """
  if mesh is not None and mesh_spec is not None:
    raise ValueError("pass mesh OR mesh_spec, not both")
  state = {"calls": 0}

  def _mesh():
    if mesh is not None:
      return mesh
    if mesh_spec is None:
      return None
    # cache OUTSIDE the closure, reached via an IMPORT at call time: a
    # live Mesh stashed in `state` — or in a module global this dynamic
    # closure referenced directly, which cloudpickle serializes BY VALUE —
    # would ride along when export_bundle pickles predict_fn and crash on
    # the PJRT device objects the moment the fn was smoke-served first
    import tensorflowonspark_tpu.models.transformer as _self
    key = tuple(sorted(mesh_spec.degrees().items()))
    m = _self._SERVING_MESH_CACHE.get(key)
    if m is None:
      from tensorflowonspark_tpu.parallel import mesh as mesh_lib
      m = _self._SERVING_MESH_CACHE[key] = mesh_lib.build_mesh(mesh_spec)
    return m

  def _engine(params):
    # cache OUTSIDE the closure, reached via an IMPORT at call time (the
    # _SERVING_MESH_CACHE pickling rationale). One engine per serving
    # config AND param CONTENT; rebuilt if the caller serves a different
    # param tree.
    import tensorflowonspark_tpu.models.transformer as _self
    from tensorflowonspark_tpu.serving import ServingEngine
    from tensorflowonspark_tpu.utils.checkpoint import params_fingerprint
    # the key carries a CONTENT fingerprint of the params, not an object
    # identity: a republished model of the same shape (the registry
    # continuous-deployment loop re-serving a bundle after a new version
    # lands) previously hit the config-only key and served STALE weights
    # from the cached engine whenever the new tree aliased the old one.
    # Fingerprinting is one pass over the leaves — amortized across the
    # whole ragged partition a cache hit serves. The identity fast path
    # stays: pipeline.load_bundle memoizes (params, predict_fn) per
    # export_dir, so steady-state serves hand back the SAME pytree
    # object and skip the hash entirely.
    cfg_key = (cfg, num_steps, eos_id, pad_id, num_slots, repr(mesh_spec),
               None if mesh is None else id(mesh))
    for k, (p, eng) in list(_self._SERVING_ENGINE_CACHE.items()):
      if k[:len(cfg_key)] == cfg_key and p is params and eng.alive:
        return eng
    key = cfg_key + (params_fingerprint(params),)
    cached = _self._SERVING_ENGINE_CACHE.get(key)
    # a dead engine (loop thread died on an error) must be rebuilt, not
    # returned — otherwise one bad batch poisons ragged serving forever
    if cached is not None and cached[1].alive:
      return cached[1]
    # retire every engine under this serving config (the stale version
    # AND any dead same-version entry): drain finishes every request the
    # old engine already accepted (bounded), THEN stops it — in-flight
    # work from concurrent transform partitions is never shed. A dead
    # engine drains instantly (its loop cannot make progress).
    for k in [k for k in _self._SERVING_ENGINE_CACHE
              if k == key or k[:len(cfg_key)] == cfg_key]:
      _self._SERVING_ENGINE_CACHE.pop(k)[1].drain(
          timeout=_self._SERVING_ENGINE_DRAIN_TIMEOUT)
    # admission bounds OFF for this internal path: the transform feed is
    # already bounded (yield_batch caps rows per predict call) and has
    # no retry story — the client-facing TOS_SERVE_MAX_QUEUE* defaults
    # would turn a big ragged partition into a hard failure that the
    # pre-robustness engine served fine. Direct ServingEngine users
    # keep the bounds.
    eng = ServingEngine(params, cfg, num_slots=num_slots, eos_id=eos_id,
                        pad_id=pad_id, max_new_tokens=num_steps,
                        max_queue=0, max_queued_tokens=0,
                        mesh=_mesh()).start()
    _self._SERVING_ENGINE_CACHE[key] = (params, eng)
    return eng

  def predict_fn(params, batch):
    import zlib
    import numpy as np
    raw = next(iter(batch.values()))
    rows, ragged = _prompt_rows(raw)
    if ragged:
      # mixed-length generation: the continuous-batching engine decodes
      # each row to ITS own length/stop instead of a padded lockstep loop
      if temperature > 0:
        raise ValueError(
            "variable-length serving batches decode through the "
            "continuous-batching engine, which is greedy-only — "
            "temperature > 0 needs equal-length prompts")
      eng = _engine(params)
      outs = eng.generate(rows, max_new_tokens=num_steps)
      width = max(len(o) for o in outs)
      padded = np.full((len(outs), width), pad_id, np.int32)
      for i, o in enumerate(outs):
        padded[i, :len(o)] = o
      return {"tokens": padded}
    # an object/list column whose rows happen to share one length is NOT
    # ragged — but np.asarray on the object array would still raise, so
    # stack the already-normalized rows
    prompts = np.stack(rows) if rows is not None else \
        np.asarray(raw, np.int32)
    if prompts.ndim == 1:          # one column of scalar token ids
      prompts = prompts[:, None]
    rng = None
    if temperature > 0:
      state["calls"] += 1
      rng = jax.random.fold_in(
          jax.random.fold_in(jax.random.PRNGKey(seed),
                             zlib.crc32(prompts.tobytes())),
          state["calls"])
    out = greedy_generate_kv(params, cfg, jnp.asarray(prompts), num_steps,
                             temperature=temperature, top_k=top_k, rng=rng,
                             mesh=_mesh(), eos_id=eos_id, pad_id=pad_id)
    return {"tokens": np.asarray(out)}

  return predict_fn


def causal_lm_loss(logits, tokens, z_loss: float = 0.0):
  """Next-token cross-entropy (shifted); ignores the final position.

  ``z_loss`` > 0 adds the auxiliary ``z_loss · mean(logsumexp²)`` term
  (PaLM/T5X recipe, typically 1e-4): it pulls the partition function
  toward 1, stabilizing bf16 logit growth over long runs — cheap
  insurance on TPU where the softmax runs in bf16-accumulated f32.
  """
  import optax
  targets = tokens[:, 1:]
  logits = logits[:, :-1]
  ce = optax.softmax_cross_entropy_with_integer_labels(
      logits, targets).mean()
  if z_loss:
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    ce = ce + z_loss * jnp.mean(lse ** 2)
  return ce


def tied_embedding_table(params):
  """The tied input/output embedding [vocab, d_model] from a Transformer
  param tree (unboxing flax ``Partitioned`` leaves if present)."""
  table = params["embed"]["embedding"]
  if hasattr(table, "unbox"):
    table = table.unbox()
  return table


def causal_lm_loss_blocked(hidden, table, tokens, chunk: int = 256,
                           z_loss: float = 0.0):
  """Next-token cross-entropy fused with the tied output projection.

  The [batch, seq, vocab] logits are never materialized: sequence chunks
  of ``chunk`` positions are projected against ``table``, reduced to
  (logsumexp, label logit), and discarded; ``jax.checkpoint`` around the
  chunk body makes the backward recompute each chunk's logits in turn, so
  peak activation memory is [batch, chunk, vocab] instead of
  [batch, seq, vocab] (a vocab-sized factor — ~2 GB down to ~500 MB at
  the bench config, which is what bounded the trainable batch size).

  ``hidden``: final-layer-norm output from
  ``model.apply(..., return_hidden=True)`` [B, S, D]; ``table``: tied
  embedding [V, D] (:func:`tied_embedding_table`). Matches
  :func:`causal_lm_loss` on the same inputs (including ``z_loss``) to
  float tolerance — the per-chunk logsumexp the reduction already
  computes feeds the z-term for free.
  """
  targets = tokens[:, 1:]
  x = hidden[:, :-1]
  b, s, d = x.shape
  n = -(-s // chunk)
  pad = n * chunk - s
  if pad:
    x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    targets = jnp.pad(targets, ((0, 0), (0, pad)))
  mask = (jnp.arange(n * chunk) < s).astype(jnp.float32)
  xs = x.reshape(b, n, -1, d).transpose(1, 0, 2, 3)     # [n, B, C, D]
  ts = targets.reshape(b, n, -1).transpose(1, 0, 2)     # [n, B, C]
  ms = mask.reshape(n, -1)                              # [n, C]
  tbl = table.astype(x.dtype)

  @jax.checkpoint
  def body(carry, inp):
    tot, z_tot = carry
    xc, tc, mc = inp
    logits = jnp.einsum("bcd,vd->bcv", xc, tbl,
                        preferred_element_type=jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)             # [B, C]
    ll = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
    return (tot + jnp.sum((lse - ll) * mc[None, :]),
            z_tot + jnp.sum(lse ** 2 * mc[None, :])), None

  (total, z_total), _ = jax.lax.scan(
      body, (jnp.float32(0.0), jnp.float32(0.0)), (xs, ts, ms))
  loss = total / (b * s)
  if z_loss:
    loss = loss + z_loss * z_total / (b * s)
  return loss


def _init_fns(rng, cfg: TransformerConfig, mesh, learning_rate, seq_len,
              init_batch: int = 1, tx=None):
  """(params_init_fn, make_state_fn) pair for parallel.sharding init.

  ``tx``: any optax GradientTransformation (see :mod:`optim` for the
  schedule/clipping recipe builder); defaults to plain AdamW at
  ``learning_rate``."""
  import optax
  from flax.training import train_state

  if cfg.sparse_topk:
    raise ValueError(sparse_refusal(
        cfg, "a training state (create_state / create_sharded_state)",
        "train"))
  model = Transformer(cfg, mesh)
  tokens = jnp.zeros((init_batch, seq_len), jnp.int32)

  def params_init():
    return model.init(rng, tokens)["params"]  # Partitioned-boxed

  def make_state(params):
    opt = tx if tx is not None else         optax.adamw(learning_rate, weight_decay=0.01)
    return train_state.TrainState.create(apply_fn=model.apply,
                                         params=params, tx=opt)

  return params_init, make_state


def create_state(rng, cfg: TransformerConfig,
                 learning_rate: float = 3e-4, seq_len: int = 128,
                 tx=None):
  """Single-device TrainState (params unboxed, unsharded)."""
  from flax.core import meta
  params_init, make_state = _init_fns(rng, cfg, None, learning_rate,
                                      seq_len, tx=tx)
  return make_state(meta.unbox(params_init()))


def create_sharded_state(rng, cfg: TransformerConfig, mesh,
                         learning_rate: float = 3e-4, seq_len: int = 128,
                         tx=None):
  """TrainState initialized directly onto the mesh (TP/FSDP layouts applied
  at init — large models never materialize replicated).

  Returns (state, state_sharding).
  """
  from tensorflowonspark_tpu.parallel import sharding as sh
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib
  # the init trace must itself be shardable: batch covers the data axes
  init_batch = mesh_lib.axis_size(mesh, mesh_lib.AXIS_DATA,
                                  mesh_lib.AXIS_FSDP)
  params_init, make_state = _init_fns(rng, cfg, mesh, learning_rate, seq_len,
                                      init_batch=init_batch, tx=tx)
  return sh.init_sharded_state(params_init, make_state, mesh)


# ---------------------------------------------------------------------------
# Pipeline-parallel training (1F1B over the block stack)
# ---------------------------------------------------------------------------

def pipeline_partition_params(params, n_stages: int):
  """Split a Transformer param tree for the 1F1B pipeline.

  Returns ``(outer_params, stage_params)``: the embedding table and final
  norm stay outer (first/last stage work); the homogeneous ``layer_i``
  blocks stack into ``[n_stages, layers_per_stage, ...]`` leaves, stage
  ``s`` owning the contiguous chunk ``[s*k, (s+1)*k)``.
  """
  num_layers = sum(1 for k in params if k.startswith("layer_"))
  assert num_layers % n_stages == 0, \
      "%d layers do not split into %d stages" % (num_layers, n_stages)
  k = num_layers // n_stages
  layers = [params["layer_%d" % i] for i in range(num_layers)]
  stage = jax.tree.map(
      lambda *ls: jnp.stack(ls).reshape((n_stages, k) + ls[0].shape), *layers)
  # everything that is not a pipelined block is outer (first/last stage
  # work) — keyed negatively so model variants with extra top-level params
  # (untied head, learned positions) are carried instead of silently lost
  outer = {key: v for key, v in params.items()
           if not key.startswith("layer_")}
  return outer, stage


def pipeline_unpartition_grads(g_outer, g_stage, num_layers: int):
  """Rebuild the full param-tree layout from pipeline grads."""
  flat = jax.tree.map(
      lambda g: g.reshape((num_layers,) + g.shape[2:]), g_stage)
  tree = dict(g_outer)
  for i in range(num_layers):
    tree["layer_%d" % i] = jax.tree.map(lambda g, _i=i: g[_i], flat)
  return tree


def make_pipeline_train_step(cfg: TransformerConfig, mesh,
                             num_microbatches: int):
  """A ``(params, tokens) -> (loss, grads)`` step training the Transformer
  with the 1F1B schedule over the mesh's ``pipeline`` axis.

  Stage sharding is explicit in the pipeline's shard_map, so blocks run
  with ``mesh=None`` (no inner sharding constraints); the embed runs on
  the first stage and the final-norm + tied projection + loss on the last,
  via ``parallel.pipeline_parallel.pipeline_lm_train_step`` — the tied
  table's embed- and head-side grad contributions are summed across those
  stages. Homogeneous layers only (``moe_experts == 0``: MoE layers have a
  different param tree and cannot stack into uniform stages).
  """
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib
  from tensorflowonspark_tpu.parallel import pipeline_parallel as PP

  assert cfg.moe_experts == 0, "pipeline stages must be homogeneous"
  if cfg.sparse_topk:
    raise ValueError(sparse_refusal(cfg, "the pipeline train step", "train"))
  n_stages = mesh.shape[mesh_lib.AXIS_PIPELINE]
  # honor cfg.remat like the dense path does: the per-microbatch stage vjp
  # otherwise stores every intra-block intermediate for all
  # layers-per-stage blocks — the regime where remat matters most
  block = (_remat_block(cfg) if cfg.remat else Block)(cfg, None)
  embed_mod = TiedEmbed(cfg, None)
  ln_f = _make_layer_norm(cfg, None, "ln_f")

  def embed_fn(outer, tokens):
    x = embed_mod.apply({"params": outer["embed"]}, tokens)
    return x.astype(cfg.dtype)

  def stage_fn(stage_p, x):
    positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])

    def body(carry, layer_p):
      return block.apply({"params": layer_p}, carry, positions), None

    x, _ = lax.scan(body, x, stage_p)
    return x

  def head_loss_fn(outer, x, targets):
    x = ln_f.apply({"params": outer["ln_f"]}, x)
    # the one tied-projection definition (TiedEmbed.attend), not a copy
    logits = embed_mod.apply({"params": outer["embed"]}, x.astype(cfg.dtype),
                             method="attend")
    return causal_lm_loss(logits.astype(jnp.float32), targets)

  def partitioned_step(outer, stage, tokens):
    """(outer_params, stage_params, tokens) -> (loss, g_outer, g_stage) —
    for training loops that keep params (and optimizer state) in the
    pipeline layout across steps, avoiding the per-step restack."""
    return PP.pipeline_lm_train_step(
        embed_fn, stage_fn, head_loss_fn, outer, stage, tokens, tokens,
        mesh, num_microbatches)

  def step(params, tokens):
    # convenience layout: restacks the layer tree each step — fine for
    # validation/small models; large-scale loops should hold the
    # partitioned layout and call ``step.partitioned`` directly
    outer, stage = pipeline_partition_params(params, n_stages)
    loss, g_outer, g_stage = partitioned_step(outer, stage, tokens)
    return loss, pipeline_unpartition_grads(g_outer, g_stage,
                                            cfg.num_layers)

  step.partitioned = partitioned_step
  return step
