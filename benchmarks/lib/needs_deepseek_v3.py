"""What a decode step of the ``deepseek-v3`` configuration NEEDS to move
through HBM, from the configuration's sizes and the program's counters (not
what a program happens to execute), for ``decode_step_needed_gb_s.deepseek``;
and what ONE call of the decode-attention kernel over a latent leaf needs to
read and to multiply, for ``decode_attention_roofline.deepseek``.

A step of the cut in ``benchmarks/configs/deepseek-v3.json`` is bound by bytes
in its matrix products (32 tokens wide) and sits AT the chip's ridge in its
attention: 128 heads share one latent row of 1280 B, so a row read once feeds
128 x 2 x (576 + 512) FLOP.  Per step, for the lanes that are LIVE:

* the weights every token passes, read once whatever the batch: a layer's
  attention (the query's two maps through its rank, the latent's map, the
  expansion ``kv_b`` that the absorbed read multiplies into the query and onto
  the output, ``out``), the dense layer's MLP, each expert layer's SHARED
  expert, router, norms and the output head (the embedding is a gather of one
  row a lane: left out);
* the routed experts that got at least one live token, three matrices each
  (the program's ``moe_experts_touched`` counts them a layer-step);
* the cache READ: every layer reads a lane's whole context
  (``live_context_tokens``) at 640 lanes x 2 B = 1280 B a token, ONCE for
  scores and values;
* the cache WRITTEN: one such row a live lane a layer.

Activations are left out (a few MB).
"""

import os

from benchmarks.lib import loader

CONFIG = os.path.join(loader.HERE, "configs", "deepseek-v3.json")
F32, BF16 = 4, 2
LANES = 128


def sizes(config: dict = None) -> dict:
  """Parameter counts and cache sizes by part, from the configuration file's
  own keys."""
  c = config or loader.load_json(CONFIG)
  d, h = c["hidden_size"], c["num_attention_heads"]
  qr, r = c["q_lora_rank"], c["kv_lora_rank"]
  dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
  layers, n_dense = c["num_hidden_layers"], c["first_k_dense_replace"]
  n_exp = layers - n_dense
  routed = c.get("n_routed_experts_published", c["n_routed_experts"])
  expert = 3 * d * c["moe_intermediate_size"]
  attention = d * qr + qr * h * (dn + dr) + d * (r + dr) \
      + r * h * (dn + dv) + h * dv * d
  return dict(
      layers=layers, dense_layers=n_dense, expert_layers=n_exp, heads=h,
      held=c["n_routed_experts"], routed=routed, top_k=c["num_experts_per_tok"],
      groups=c["n_group"], groups_kept=c["topk_group"],
      expert_params=expert, latent=r + dr, latent_values=r,
      # a token's row of ONE layer's leaf: the latent in whole lanes
      token_bytes=-(-(r + dr) // LANES) * LANES * BF16,
      attention_params=attention + qr + r,         # with its two norm scales
      # bf16 matrices every token passes
      dense_params=layers * attention + n_dense * 3 * d * c["intermediate_size"]
      + n_exp * c["n_shared_experts"] * expert + d * c["vocab_size"],
      # float32 leaves every token passes: norm scales, router and its bias
      f32_params=layers * (2 * d + qr + r) + d + n_exp * (d + 1) * routed,
      embed_params=c["vocab_size"] * d)


def param_count(config: dict = None) -> int:
  """Parameters as built (the family's ``param_count``, from the sizes)."""
  z = sizes(config)
  return (z["dense_params"] + z["embed_params"] + z["f32_params"]
          + z["expert_layers"] * z["held"] * z["expert_params"])


def layer_params(config: dict = None) -> dict:
  """Parameters of one layer by kind, as the hand count has them: the dense
  layer and an expert layer HERE (outside its routed experts, and with the
  held ones)."""
  c = config or loader.load_json(CONFIG)
  z, d = sizes(c), c["hidden_size"]
  outside = z["attention_params"] + 2 * d + (d + 1) * z["routed"] \
      + c["n_shared_experts"] * z["expert_params"]
  return dict(
      attention=z["attention_params"],
      dense=z["attention_params"] + 2 * d + 3 * d * c["intermediate_size"],
      expert_outside_routed=outside,
      expert=outside + z["held"] * z["expert_params"],
      ends=2 * c["vocab_size"] * d + d)


def weight_bytes(config: dict = None) -> float:
  """Bytes of the model as built (bf16 matrices, float32 scales and
  router)."""
  z = sizes(config)
  return (z["dense_params"] + z["embed_params"]
          + z["expert_layers"] * z["held"] * z["expert_params"]) * BF16 \
      + z["f32_params"] * F32


def passed_bytes(config: dict = None) -> float:
  """Bytes of the weights EVERY token passes (no routed expert; the
  embedding is a gather of one row)."""
  z = sizes(config)
  return z["dense_params"] * BF16 + z["f32_params"] * F32


def slab_bytes(slots: int, max_seq: int, config: dict = None) -> float:
  """Bytes of the serving slab: one latent leaf a layer."""
  z = sizes(config)
  return slots * max_seq * z["layers"] * z["token_bytes"]


def decode_step_bytes(live_lanes: float, experts_touched: float,
                      context_tokens: float, config: dict = None) -> float:
  """Bytes ONE decode step needs: ``live_lanes`` the mean number of live
  lanes, ``experts_touched`` the held experts with at least one live token
  summed over the expert layers, ``context_tokens`` the tokens the live
  lanes' caches hold."""
  z = sizes(config)
  per_token = z["layers"] * z["token_bytes"]
  return passed_bytes(config) + experts_touched * z["expert_params"] * BF16 \
      + (context_tokens + live_lanes) * per_token


def decode_attention_bytes(rows: float, config: dict = None) -> float:
  """Bytes ONE call of the decode-attention kernel needs to read of its
  latent leaf: ``rows`` the LIVE rows, summed over the slots, each ONCE (it
  is key and value). Whole blocks read past a cursor, the queries, the step's
  own row and the output are not counted."""
  return rows * sizes(config)["token_bytes"]


def decode_attention_flops(rows: float, config: dict = None) -> float:
  """FLOPs the same call needs: every head's absorbed query against the
  row's ``rank + rope`` numbers (scores) and every head's probability onto its
  ``rank`` values, 2 FLOP a multiply-add; the lanes of padding and the
  probabilities' further bf16 terms are not counted."""
  z = sizes(config)
  return rows * z["heads"] * 2.0 * (z["latent"] + z["latent_values"])


def counters(report):
  """The window's deltas of the program's counters this file needs, or
  ``None`` where the program has none (a parent without these layers) or
  the window saw no step."""
  d = report.get("stats_delta") or {}
  keys = ("steps", "live_slot_steps", "moe_assignments_held",
          "moe_experts_touched", "moe_group_hits", "live_context_tokens",
          "decode_attn_reads", "decode_attn_reads_ragged")
  if any(k not in d for k in keys) or not d["steps"] \
      or not d["live_slot_steps"]:
    return None
  return {k: d[k] for k in keys}
