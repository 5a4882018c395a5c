"""What a decode step of the ``kimi-linear-48b-a3b`` configuration NEEDS to
move through HBM, from the configuration's sizes and the program's counters
(not what a program happens to execute), for ``decode_step_needed_gb_s.kimi``.

A step of the cut in ``benchmarks/configs/kimi-linear-48b-a3b.json`` is bound
by bytes (its matrix products are a few tokens wide: 0.05 ms of expert FLOPs
against some 14 ms of bytes at the published peaks), so the roof is bytes
over the HBM peak.  Per step, for the lanes that are LIVE:

* the KDA state, read and written once a live lane a KDA layer (float32
  ``heads x d_k x d_v``), and the convolution tail likewise (float32 as the
  configuration's ``float32_activations`` has the program store it: the
  projections' own dtype);
* the weights every token passes, read once whatever the batch: KDA, MLA,
  shared expert, router, the dense layer's MLP, norms and the output head
  (the embedding is a gather of one row a lane: left out);
* the routed experts that got at least one live token, three matrices each
  (the program's ``moe_experts_touched`` counts them a layer-step);
* the latent cache, one read of ``kv_lora_rank + qk_rope_head_dim`` numbers
  a context token a MLA layer (``live_context_tokens``; the lane padding of
  the stored leaf is the program's choice and is not needed).

Activations are left out (a few MB).
"""

import os

from benchmarks.lib import loader

CONFIG = os.path.join(loader.HERE, "configs", "kimi-linear-48b-a3b.json")
F32, BF16 = 4, 2


def sizes(config: dict = None) -> dict:
  """Parameter counts by part, from the configuration file's own keys."""
  c = config or loader.load_json(CONFIG)
  lin = c["linear_attn_config"]
  d, h, dk = c["hidden_size"], lin["num_heads"], lin["head_dim"]
  w, r = h * dk, c["kda_low_rank_dim"]
  kda = 3 * d * w + w * d + 2 * (d * r + r * w) + d * h \
      + lin["short_conv_kernel_size"] * 3 * w
  hq = c["num_attention_heads"]
  qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
  latent = c["kv_lora_rank"] + c["qk_rope_head_dim"]
  mla = d * hq * qk + d * latent \
      + c["kv_lora_rank"] * hq * (c["qk_nope_head_dim"] + c["v_head_dim"]) \
      + hq * c["v_head_dim"] * d
  expert = 3 * d * c["moe_intermediate_size"]
  n_kda, n_mla = len(lin["kda_layers"]), len(lin["full_attn_layers"])
  n_dense = c["first_k_dense_replace"]
  n_exp = c["num_hidden_layers"] - n_dense
  return dict(
      kda_layers=n_kda, mla_layers=n_mla, expert_layers=n_exp,
      held=c["num_experts"], expert_params=expert, latent=latent,
      state_numbers=h * dk * dk,
      tail_numbers=(lin["short_conv_kernel_size"] - 1) * 3 * w,
      tail_bytes=F32 if c.get("float32_activations") else BF16,
      # bf16 matrices every token passes
      dense_params=n_kda * kda + n_mla * mla
      + n_exp * c["num_shared_experts"] * expert
      + n_dense * 3 * d * c["intermediate_size"] + d * c["vocab_size"],
      # float32 leaves every token passes: the router and its bias
      f32_params=n_exp * (d + 1) * c["num_experts_published"])


def decode_step_bytes(live_lanes: float, experts_touched: float,
                      context_tokens: float, config: dict = None) -> float:
  """Bytes ONE decode step needs: ``live_lanes`` the mean number of live
  lanes, ``experts_touched`` the held experts with at least one live token
  summed over the expert layers, ``context_tokens`` the tokens the live
  lanes' caches hold."""
  z = sizes(config)
  state = 2 * live_lanes * z["kda_layers"] \
      * (z["state_numbers"] * F32 + z["tail_numbers"] * z["tail_bytes"])
  weights = z["dense_params"] * BF16 + z["f32_params"] * F32
  experts = experts_touched * z["expert_params"] * BF16
  cache = context_tokens * z["mla_layers"] * z["latent"] * BF16
  return state + weights + experts + cache


def counters(report):
  """The window's deltas of the program's counters this file needs, or
  ``None`` where the program has none (a parent without these layers) or
  the window saw no step."""
  d = report.get("stats_delta") or {}
  keys = ("steps", "live_slot_steps", "moe_assignments_held",
          "moe_experts_touched", "live_context_tokens")
  if any(k not in d for k in keys) or not d["steps"] \
      or not d["live_slot_steps"]:
    return None
  return {k: d[k] for k in keys}
