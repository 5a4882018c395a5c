"""What a decode step of the ``mimo-v2-flash`` configuration NEEDS to move
through HBM, from the configuration's sizes and the program's counters (not
what a program happens to execute), for ``decode_step_needed_gb_s.mimo``; and
what ONE call of the decode-attention kernel needs to read, by the kind of
leaf it reads, for ``decode_attention_roofline.mimo``.

A step of the cut in ``benchmarks/configs/mimo-v2-flash.json`` is bound by
bytes (its matrix products are 32 tokens wide), so the roof is bytes over the
HBM peak.  Per step, for the lanes that are LIVE:

* the weights every token passes, read once whatever the batch: attention
  (q, k, v, out: K and V of 4 heads in a full layer, of 8 in a window layer;
  keys of 192 and values of 128), router, the dense layer's MLP, norms, sinks
  and the output head (the embedding is a gather of one row a lane: left
  out); there is no shared expert;
* the routed experts that got at least one live token, three matrices each
  (the program's ``moe_experts_touched`` counts them a layer-step);
* the cache READ: a FULL layer reads a lane's whole context
  (``live_context_tokens``) at ``(4 x 192 + 4 x 128) x 2`` = 2560 B a token, a
  WINDOW layer only the rows of its window (``window_context_tokens``: the sum
  of ``min(cursor, 128)``) at ``(8 x 192 + 8 x 128) x 2`` = 5120 B a token;
* the cache WRITTEN: one such row a live lane a layer.

Activations are left out (a few MB).
"""

import os

from benchmarks.lib import loader

CONFIG = os.path.join(loader.HERE, "configs", "mimo-v2-flash.json")
F32, BF16 = 4, 2


def sizes(config: dict = None) -> dict:
  """Parameter counts and cache sizes by part, from the configuration file's
  own keys."""
  c = config or loader.load_json(CONFIG)
  d, dk, dv = c["hidden_size"], c["head_dim"], c["v_head_dim"]
  h = c["num_attention_heads"]
  layers = c["num_hidden_layers"]
  window = [bool(k) for k in c["hybrid_layer_pattern"][:layers]]
  sparse = [bool(k) for k in c["moe_layer_freq"][:layers]]
  n_window, n_exp = sum(window), sum(sparse)
  n_full, n_dense = layers - n_window, layers - n_exp
  kv = {False: c["num_key_value_heads"], True: c["swa_num_key_value_heads"]}
  token = {w: kv[w] * (dk + dv) * BF16 for w in kv}   # K and V a token a layer
  attention = {w: d * h * dk + d * kv[w] * (dk + dv) + h * dv * d for w in kv}
  sinks = h * (n_window * bool(c["add_swa_attention_sink_bias"])
               + n_full * bool(c["add_full_attention_sink_bias"]))
  expert = 3 * d * c["moe_intermediate_size"]
  routed = c.get("n_routed_experts_published", c["n_routed_experts"])
  return dict(
      layers=layers, window_layers=n_window, full_layers=n_full,
      expert_layers=n_exp, held=c["n_routed_experts"], expert_params=expert,
      window=c["sliding_window"],
      full_token_bytes=token[False], window_token_bytes=token[True],
      # bf16 matrices every token passes
      dense_params=n_full * attention[False] + n_window * attention[True]
      + n_dense * 3 * d * c["intermediate_size"] + d * c["vocab_size"],
      # float32 leaves every token passes: norm scales, router and its bias,
      # sinks
      f32_params=layers * 2 * d + d + n_exp * (d + 1) * routed + sinks,
      embed_params=c["vocab_size"] * d)


def param_count(config: dict = None) -> int:
  """Parameters as built (the family's ``param_count``, from the sizes)."""
  z = sizes(config)
  return (z["dense_params"] + z["embed_params"] + z["f32_params"]
          + z["expert_layers"] * z["held"] * z["expert_params"])


def weight_bytes(config: dict = None) -> float:
  """Bytes of the model as built (bf16 matrices, float32 scales, router and
  sinks)."""
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
  """Bytes of the serving slab: a whole-context leaf pair a full layer, a
  ring of the window's rows a window layer."""
  z = sizes(config)
  return slots * (z["full_layers"] * z["full_token_bytes"] * max_seq
                  + z["window_layers"] * z["window_token_bytes"]
                  * min(z["window"], max_seq))


def decode_step_bytes(live_lanes: float, experts_touched: float,
                      context_tokens: float, window_tokens: float,
                      config: dict = None) -> float:
  """Bytes ONE decode step needs: ``live_lanes`` the mean number of live
  lanes, ``experts_touched`` the held experts with at least one live token
  summed over the expert layers, ``context_tokens`` the tokens the live
  lanes' caches hold, ``window_tokens`` those of them inside a window."""
  z = sizes(config)
  experts = experts_touched * z["expert_params"] * BF16
  per_lane = (z["full_layers"] * z["full_token_bytes"]
              + z["window_layers"] * z["window_token_bytes"])
  read = context_tokens * z["full_layers"] * z["full_token_bytes"] \
      + window_tokens * z["window_layers"] * z["window_token_bytes"]
  return passed_bytes(config) + experts + read + live_lanes * per_lane


def decode_attention_bytes(rows: float, ring: bool,
                           config: dict = None) -> float:
  """Bytes of K and V ONE call of the decode-attention kernel needs to read:
  ``rows`` the LIVE rows of the leaf pair it reads, summed over the slots
  (a full layer's: the lanes' contexts; a ring's: ``min(cursor, window)`` a
  lane), at the leaf kind's bytes a row. Whole blocks read past a cursor,
  the queries, the step's own key and value and the output are not
  counted: they are not what the attention NEEDS of the cache."""
  z = sizes(config)
  return rows * (z["window_token_bytes"] if ring else z["full_token_bytes"])


def counters(report):
  """The window's deltas of the program's counters this file needs, or
  ``None`` where the program has none (a parent without these layers) or
  the window saw no step."""
  d = report.get("stats_delta") or {}
  keys = ("steps", "live_slot_steps", "moe_assignments_held",
          "moe_experts_touched", "live_context_tokens",
          "window_context_tokens", "decode_attn_reads",
          "decode_attn_reads_ring")
  if any(k not in d for k in keys) or not d["steps"] \
      or not d["live_slot_steps"]:
    return None
  return {k: d[k] for k in keys}
