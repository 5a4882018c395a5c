"""What a decode step of the ``trinity-large-preview`` configuration NEEDS to
move through HBM, from the configuration's sizes and the program's counters
(not what a program happens to execute), for ``decode_step_needed_gb_s.trinity``.

A step of the cut in ``benchmarks/configs/trinity-large-preview.json`` is
bound by bytes (its matrix products are 24 tokens wide), so the roof is bytes
over the HBM peak.  Per step, for the lanes that are LIVE:

* the weights every token passes, read once whatever the batch: attention
  (q, k, v, gate, out), shared expert, router, the dense layer's MLP, norms
  and the output head (the embedding is a gather of one row a lane: left out);
* the routed experts that got at least one live token, three matrices each
  (the program's ``moe_experts_touched`` counts them a layer-step);
* the cache READ: a FULL layer reads a lane's whole context
  (``live_context_tokens``), a WINDOW layer only the rows of its window
  (``window_context_tokens``: the sum of ``min(cursor, window)``), K and V
  of ``num_key_value_heads x head_dim`` bf16 numbers a token a layer;
* the cache WRITTEN: one such row a live lane a layer.

Activations are left out (a few MB).
"""

import os

from benchmarks.lib import loader

CONFIG = os.path.join(loader.HERE, "configs", "trinity-large-preview.json")
F32, BF16 = 4, 2


def sizes(config: dict = None) -> dict:
  """Parameter counts and cache sizes by part, from the configuration file's
  own keys."""
  c = config or loader.load_json(CONFIG)
  d, dh = c["hidden_size"], c["head_dim"]
  wq, wkv = c["num_attention_heads"] * dh, c["num_key_value_heads"] * dh
  layers, n_dense = c["num_hidden_layers"], c["num_dense_layers"]
  first = c.get("first_layer_published", 1) - 1
  kinds = c["layer_types"][first:first + layers]
  n_window = sum(k == "sliding_attention" for k in kinds)
  n_exp = layers - n_dense
  attention = 3 * d * wq + 2 * d * wkv          # q, gate, out; k, v
  expert = 3 * d * c["moe_intermediate_size"]
  norms = 4 * d + 2 * dh                        # float32 scales a layer
  return dict(
      layers=layers, window_layers=n_window, full_layers=layers - n_window,
      expert_layers=n_exp, held=c["num_experts"], expert_params=expert,
      window=c["sliding_window"],
      token_bytes=2 * wkv * BF16,               # K and V of a token a layer
      # bf16 matrices every token passes
      dense_params=layers * attention
      + n_exp * c["num_shared_experts"] * expert
      + n_dense * 3 * d * c["intermediate_size"] + d * c["vocab_size"],
      # float32 leaves every token passes: norm scales, router and its bias
      f32_params=layers * norms + d
      + n_exp * (d + 1) * c["num_experts_published"],
      embed_params=c["vocab_size"] * d)


def weight_bytes(config: dict = None) -> float:
  """Bytes of the model as built (bf16 matrices, float32 scales and router)."""
  z = sizes(config)
  return (z["dense_params"] + z["embed_params"]
          + z["expert_layers"] * z["held"] * z["expert_params"]) * BF16 \
      + z["f32_params"] * F32


def slab_bytes(slots: int, max_seq: int, config: dict = None) -> float:
  """Bytes of the serving slab: a whole-context leaf pair a full layer, a
  ring of the window's rows a window layer."""
  z = sizes(config)
  return slots * z["token_bytes"] * (
      z["full_layers"] * max_seq + z["window_layers"] * min(z["window"],
                                                            max_seq))


def decode_step_bytes(live_lanes: float, experts_touched: float,
                      context_tokens: float, window_tokens: float,
                      config: dict = None) -> float:
  """Bytes ONE decode step needs: ``live_lanes`` the mean number of live
  lanes, ``experts_touched`` the held experts with at least one live token
  summed over the expert layers, ``context_tokens`` the tokens the live
  lanes' caches hold, ``window_tokens`` those of them inside a window."""
  z = sizes(config)
  weights = z["dense_params"] * BF16 + z["f32_params"] * F32
  experts = experts_touched * z["expert_params"] * BF16
  read = z["token_bytes"] * (context_tokens * z["full_layers"]
                             + window_tokens * z["window_layers"])
  written = live_lanes * z["token_bytes"] * z["layers"]
  return weights + experts + read + written


def counters(report):
  """The window's deltas of the program's counters this file needs, or
  ``None`` where the program has none (a parent without these layers) or
  the window saw no step."""
  d = report.get("stats_delta") or {}
  keys = ("steps", "live_slot_steps", "moe_assignments_held",
          "moe_experts_touched", "live_context_tokens",
          "window_context_tokens")
  if any(k not in d for k in keys) or not d["steps"] \
      or not d["live_slot_steps"]:
    return None
  return {k: d[k] for k in keys}
