"""What a decode step of the ``ouro-2.6b`` configuration NEEDS to move through
HBM, from the configuration's sizes and the program's counters (not what a
program happens to execute), for ``decode_step_needed_gb_s.ouro``.

A step is bound by bytes (its matrix products are a few tokens wide: 0.8 ms of
FLOPs for 8 tokens against some 25 ms of bytes at the published peaks), so the
roof is bytes over the HBM peak.  Per step:

* the layers' weights, read once a PASS whatever the batch: the 48 layers run
  ``total_ut_steps`` times a token over the same weights and no chip holds
  them in fast memory between passes, so ``passes x layers x layer weights``;
  the final norm and the exit gate likewise a pass; the output head once (the
  embedding is a gather of one row a lane: left out);
* the cache READ: every pass of every layer attends its own keys and values
  over the live lanes' context: ``live_context_tokens x passes x layers x 2
  x heads x head_dim`` bf16 numbers (what a slot has not filled yet is not
  needed, though a dense read moves it);
* the cache WRITTEN: one row a live lane in each of the ``passes x layers x
  2`` leaves.

Activations are left out (a few MB).
"""

import os

from benchmarks.lib import loader

CONFIG = os.path.join(loader.HERE, "configs", "ouro-2.6b.json")
F32, BF16 = 4, 2


def sizes(config: dict = None) -> dict:
  """Parameter and cache counts by part, from the configuration file's own
  keys."""
  c = config or loader.load_json(CONFIG)
  d, f = c["hidden_size"], c["intermediate_size"]
  w = c["num_attention_heads"] * c["head_dim"]
  return dict(
      passes=c["total_ut_steps"], layers=c["num_hidden_layers"],
      # bf16 matrices of one layer, and its four float32 norm scales
      layer_params=4 * d * w + 3 * d * f, layer_f32=4 * d,
      # a pass's own float32 leaves: the final norm and the exit gate
      pass_f32=2 * d + 1,
      head_params=d * c["vocab_size"],
      # numbers a token keeps in ONE pass of ONE layer: K and V
      kv_numbers=2 * w)


def token_cache_bytes(config: dict = None) -> int:
  """Bytes of cache ONE token holds: K and V in every pass of every layer."""
  z = sizes(config)
  return z["passes"] * z["layers"] * z["kv_numbers"] * BF16


def weight_bytes(config: dict = None) -> int:
  """Bytes of weights ONE decode step reads, whatever the batch."""
  z = sizes(config)
  layer = z["layer_params"] * BF16 + z["layer_f32"] * F32
  return z["passes"] * (z["layers"] * layer + z["pass_f32"] * F32) \
      + z["head_params"] * BF16


def decode_step_bytes(live_lanes: float, context_tokens: float,
                      config: dict = None) -> float:
  """Bytes ONE decode step needs: ``live_lanes`` the mean number of live
  lanes, ``context_tokens`` the tokens the live lanes' caches hold."""
  token = token_cache_bytes(config)
  return weight_bytes(config) + (context_tokens + live_lanes) * token


def counters(report):
  """The window's deltas of the program's counters this file's readers
  need, or ``None`` where the program has none (a parent without the loop)
  or the window saw no step."""
  d = report.get("stats_delta") or {}
  keys = ("steps", "live_slot_steps", "live_context_tokens",
          "loop_exit_pass_sum")
  if any(k not in d for k in keys) or not d["steps"] \
      or not d["live_slot_steps"]:
    return None
  return {k: d[k] for k in keys}
