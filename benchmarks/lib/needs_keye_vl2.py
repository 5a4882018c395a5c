"""What a decode step of the ``keye-vl-2.0-30b-a3b`` configuration NEEDS to
move through HBM, from the configuration's sizes and the program's counters
(not what a program happens to execute), for ``decode_step_needed_gb_s.keye``;
and what ONE call of the decode-attention kernel under a selection's keep rows
needs to read, for ``decode_attention_roofline.keye``.

A step of the cut in ``benchmarks/configs/keye-vl-2.0-30b-a3b.json`` is bound
by bytes everywhere (16 tokens wide; 32 query heads over 4 KV heads of 128 are
16 FLOP a byte of K and V).  Per step, for the lanes that are LIVE:

* the weights every token passes, read once whatever the batch: a layer's
  attention (q, k, v, out), its indexer (q, k, the heads' weights), router,
  norms and the output head (the embedding is a gather of one row a lane:
  left out);
* the held experts that got at least one live token, three matrices each
  (the program's ``moe_experts_touched`` counts them a layer-step);
* the cache READ AS THE PROGRAM READS IT: every layer scores a lane's whole
  context against its index keys (256 B a token) and then reads the context's
  keys and values whole (2048 B a token) under the keep rows: 2304 B a token
  a layer.  What a read of the CHOSEN rows alone would bring is
  :func:`chosen_rows_bytes`: the index keys of every candidate and the keys
  and values of the rows kept (``sparse_rows_kept``);
* the cache WRITTEN: one row of each of the three leaves a live lane a layer.

Activations, the float32 index scores (``lanes x context x 4`` B a layer) and
the keep rows are left out.
"""

import os

from benchmarks.lib import loader

CONFIG = os.path.join(loader.HERE, "configs", "keye-vl-2.0-30b-a3b.json")
F32, BF16 = 4, 2
LANES = 128


def sizes(config: dict = None) -> dict:
  """Parameter counts and cache sizes by part, from the configuration file's
  own keys."""
  c = config or loader.load_json(CONFIG)
  sa = c["sa_config"]
  d, h, hk, dh = (c["hidden_size"], c["num_attention_heads"],
                  c["num_key_value_heads"], c["head_dim"])
  hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
  layers = c["num_hidden_layers"]
  routed = c.get("num_experts_published", c["num_experts"])
  expert = 3 * d * c["moe_intermediate_size"]
  attention = d * h * dh + 2 * d * hk * dh + h * dh * d       # bf16 matrices
  indexer = d * hi * di + d * di + d * hi                     # bf16 matrices
  index_lanes = -(-di // LANES) * LANES
  return dict(
      layers=layers, expert_layers=layers, heads=h, kv_heads=hk, head_dim=dh,
      held=c["num_experts"], routed=routed, top_k=c["num_experts_per_tok"],
      topk=sa["topk"], expert_params=expert,
      attention_params=attention + 2 * dh,        # with its two head norms
      indexer_params=indexer + 2 * di,            # with the key's LayerNorm
      # a token's row of ONE layer: keys, values, the index key's leaf
      kv_token_bytes=2 * hk * dh * BF16, index_token_bytes=index_lanes * BF16,
      token_bytes=2 * hk * dh * BF16 + index_lanes * BF16,
      # bf16 matrices every token passes
      dense_params=layers * (attention + indexer) + d * c["vocab_size"],
      # float32 leaves every token passes: norm scales and bias, the router
      f32_params=layers * (2 * d + 2 * dh + 2 * di + d * routed) + d,
      embed_params=c["vocab_size"] * d)


def param_count(config: dict = None) -> int:
  """Parameters as built (the family's ``param_count``, from the sizes)."""
  z = sizes(config)
  return (z["dense_params"] + z["embed_params"] + z["f32_params"]
          + z["expert_layers"] * z["held"] * z["expert_params"])


def layer_params(config: dict = None) -> dict:
  """Parameters of one layer by part, as the hand count has them."""
  c = config or loader.load_json(CONFIG)
  z, d = sizes(c), c["hidden_size"]
  outside = z["attention_params"] + z["indexer_params"] + 2 * d \
      + d * z["routed"]
  return dict(attention=z["attention_params"], indexer=z["indexer_params"],
              expert=z["expert_params"], outside_experts=outside,
              layer=outside + z["held"] * z["expert_params"],
              ends=2 * c["vocab_size"] * d + d)


def weight_bytes(config: dict = None) -> float:
  """Bytes of the model as built (bf16 matrices, float32 scales and
  router)."""
  z = sizes(config)
  return (z["dense_params"] + z["embed_params"]
          + z["expert_layers"] * z["held"] * z["expert_params"]) * BF16 \
      + z["f32_params"] * F32


def passed_bytes(config: dict = None) -> float:
  """Bytes of the weights EVERY token passes (no expert; the embedding is a
  gather of one row)."""
  z = sizes(config)
  return z["dense_params"] * BF16 + z["f32_params"] * F32


def slab_bytes(slots: int, max_seq: int, config: dict = None) -> float:
  """Bytes of the serving slab: three leaves a layer."""
  z = sizes(config)
  return slots * max_seq * z["layers"] * z["token_bytes"]


def row_bytes(max_seq: int, config: dict = None) -> float:
  """Bytes of one prompt's positional row cache."""
  return slab_bytes(1, max_seq, config)


def decode_step_bytes(live_lanes: float, experts_touched: float,
                      context_tokens: float, config: dict = None) -> float:
  """Bytes ONE decode step needs as the program reads the cache (whole, under
  the keep rows): ``live_lanes`` the mean number of live lanes,
  ``experts_touched`` the held experts with at least one live token summed
  over the layers, ``context_tokens`` the tokens the live lanes' caches
  hold."""
  z = sizes(config)
  per_token = z["layers"] * z["token_bytes"]
  return passed_bytes(config) + experts_touched * z["expert_params"] * BF16 \
      + (context_tokens + live_lanes) * per_token


def chosen_rows_bytes(rows_kept: float, rows_candidate: float,
                      config: dict = None) -> float:
  """Bytes a read of the CHOSEN rows alone would bring: every candidate's
  index key and the kept rows' keys and values (both summed over the layers,
  as ``sparse_rows_kept`` / ``sparse_rows_candidate`` count them)."""
  z = sizes(config)
  return rows_candidate * z["index_token_bytes"] \
      + rows_kept * z["kv_token_bytes"]


def decode_attention_bytes(rows: float, config: dict = None) -> float:
  """Bytes ONE call of the decode-attention kernel under keep rows needs to
  read: ``rows`` the LIVE rows summed over the slots, each row's keys and
  values once, and a keep entry a live row (one byte would do). Whole blocks
  read past a cursor, the queries, the step's own row and the output are not
  counted."""
  return rows * (sizes(config)["kv_token_bytes"] + 1)


def decode_attention_flops(rows: float, config: dict = None) -> float:
  """FLOPs the same call needs: every query head against a live row's key and
  its probability onto the row's value, 2 FLOP a multiply-add (masked rows
  are multiplied as the kernel is built: the roof is bytes either way)."""
  z = sizes(config)
  return rows * z["heads"] * 2.0 * 2 * z["head_dim"]


def counters(report):
  """The window's deltas of the program's counters this file needs, or
  ``None`` where the program has none (a parent without these layers) or
  the window saw no step."""
  d = report.get("stats_delta") or {}
  keys = ("steps", "live_slot_steps", "moe_assignments_held",
          "moe_experts_touched", "live_context_tokens", "decode_attn_reads",
          "decode_attn_reads_ragged", "sparse_rows_kept",
          "sparse_rows_candidate", "sparse_queries_limited",
          "sparse_prefill_queries", "sparse_prefill_limited")
  if any(k not in d for k in keys) or not d["steps"] \
      or not d["live_slot_steps"]:
    return None
  return {k: d[k] for k in keys}
