"""Analytic roofline for the bench transformer: per-config MFU ceilings.

A utilisation target wants either the number or a profile-backed ceiling
analysis. Without a chip the profile half cannot run — this tool provides
the analytic half: a first-principles FLOPs + HBM-traffic model of one
training step of the bench transformer under each sweep config, bounding
the achievable step time by max(compute_time, memory_time) and hence MFU
by compute_time / bound. The same accounting slots straight into the
measured numbers when `tools/profile_step.py` runs on silicon.

Model (per step, batch B, seq S, layers L, d_model D, d_ff F, vocab V,
heads H, params N, bf16 weights/activations = 2 bytes, f32 master
quantities = 4):

- FLOPs: PaLM accounting, ``(6N + 12·L·D·S)`` per token × B·S tokens.
- Weight traffic: read every param twice (fwd + bwd) in bf16* plus the
  optimizer update (read p, m, v + write p, m, v in f32) — remat adds
  one more fwd read of the block weights.  (*params live f32 here; cast
  streams count the f32 read.)
- Activation traffic: each kernel/HLO boundary writes its output and the
  backward reads it (or recomputes under remat). The per-layer boundary
  list DEPENDS on the fusion config — that is the point: ln_matmul /
  fuse_qkv / act_matmul remove [B,S,D]- and [B,S,F]-sized round-trips,
  and this model quantifies how much of the gap to peak each one closes.
- Logits: the [B,S,V] projection + softmax traffic (or [B,chunk,V] when
  the blocked loss is on).

Prints one JSON line per config plus a markdown table on stderr.
Usage: python tools/roofline.py [--gen v5e] [--hbm-gbps 819]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the bench model's shape, imported so this analysis can never diverge
# from what bench.py actually measures
import bench as _bench  # noqa: E402 - after sys.path insert

L, D, H, F = (_bench.TFM_LAYERS, _bench.TFM_DMODEL, _bench.TFM_HEADS,
              _bench.TFM_DFF)
V, S, B = _bench.TFM_VOCAB, _bench.TFM_SEQ, _bench.TFM_BATCH
BF16, F32 = 2, 4

# HBM bandwidth per chip generation (public figures, GB/s)
HBM_GBPS = {"v4": 1228.0, "v5e": 819.0, "v5p": 2765.0, "v6e": 1640.0}


def n_params(kv_heads=H):
  head_d = D // H
  attn = D * (H + 2 * kv_heads) * head_d + D * D      # qkv + out
  mlp = 2 * D * F
  ln = 2 * D
  return V * D + L * (attn + mlp + ln) + D            # embed + layers + ln_f


def flops_per_step(kv_heads=H, remat=None):
  """MXU FLOPs/step. Full remat ("none" policy) re-runs the forward
  matmuls in the backward: +2N per token on the 6N total (the measured
  ~21% step cost). "dots" saves MXU outputs — only elementwise (VPU)
  work recomputes, which the 6N matmul model does not count."""
  from tensorflowonspark_tpu.utils import profiler
  base = B * S * profiler.transformer_flops_per_token(
      n_params(kv_heads), L, D, S)
  return base * (8.0 / 6.0) if remat == "none" else base


def weight_traffic(remat, kv_heads=H):
  """Bytes/step for parameters + optimizer state."""
  n = n_params(kv_heads)
  reads = 3 if remat == "none" else 2   # full remat re-reads for re-fwd
  opt = 6 * F32 * n                  # adam: read p,m,v + write p,m,v
  grads = 2 * F32 * n                # grad write + read by optimizer
  return reads * F32 * n + opt + grads


def act_traffic(cfg):
  """Bytes/step for activations at kernel/HLO boundaries.

  Per layer, list the [B,S,*] tensors that cross HBM between fused
  regions (each is written by the producer, read by the consumer, and
  read again by the backward — or recomputed under remat, which swaps
  the bwd read for a re-write+read; net factor ~3x either way):

  unfused:  ln1_out[D], qkv[3D], attn_out[D], proj_out[D], ln2_out[D],
            up_out[F], gelu_out[F], down_out[D], 2 residual sums[D]
  flash attention keeps scores/probs in VMEM (else + 2·[H,S,S]).
  ln_matmul removes ln1_out (with fuse_qkv) and ln2_out.
  fuse_qkv merges 3 projections (no traffic change; fewer launches).
  act_matmul removes gelu_out.
  GQA shrinks the kv part of qkv by kv_heads/H.
  """
  kv = cfg.get("num_kv_heads") or H
  remat = cfg.get("remat")
  # Elements per token per layer, split into MXU outputs vs elementwise
  # boundaries. Save factor: ×3 for saved tensors (fwd-write + bwd-read +
  # grad-of-activation write), ×1 for transient ones (produced and
  # consumed around the recompute, never stored across fwd→bwd):
  #  - no remat: everything saved (×3)
  #  - "dots":   MXU outputs saved (×3); elementwise transient (×1)
  #  - "none":   only the per-layer block boundary [D] saved; everything
  #              else transient
  mxu = (H + 2 * kv) * (D // H)       # qkv out
  mxu += D                            # attn out (flash output)
  mxu += D                            # out-proj
  mxu += F                            # up_out (pre-gelu)
  mxu += D                            # down_out
  ew = 2 * D                          # residual adds
  if not (cfg.get("ln_matmul_impl") == "fused" and cfg.get("fuse_qkv")):
    ew += D                           # ln1_out
  if not cfg.get("ln_matmul_impl") == "fused":
    ew += D                           # ln2_out
  if not cfg.get("act_matmul_impl") == "fused":
    ew += F                           # gelu_out
  if remat == "none":
    t3, t1 = D, mxu + ew
  elif remat == "dots":
    t3, t1 = mxu, ew
  else:
    t3, t1 = mxu + ew, 0
  per_layer_bytes = BF16 * (3 * t3 + t1) * B * S
  total = L * per_layer_bytes
  # embedding lookup + final ln + logits
  total += 3 * BF16 * B * S * D * 2
  # logits: [B,S,V] write + softmax read + bwd read (blocked loss cuts
  # this to [B,chunk,V] streamed — count once either way as 3x read/write
  # of the full tensor for the unblocked default)
  total += 3 * BF16 * B * S * V
  return total


def analyze(cfg, gen, hbm_gbps):
  from tensorflowonspark_tpu.utils import profiler
  kv = cfg.get("num_kv_heads") or H
  fl = flops_per_step(kv, cfg.get("remat"))
  fl_useful = flops_per_step(kv)   # MFU counts model FLOPs, not recompute
  bytes_total = weight_traffic(cfg.get("remat"), kv) + act_traffic(cfg)
  peak = profiler.PEAK_BF16_FLOPS[gen]
  t_compute = fl / peak
  t_useful = fl_useful / peak
  t_memory = bytes_total / (hbm_gbps * 1e9)
  # two bounds bracket reality: perfect compute/HBM overlap (XLA
  # pipelines transfers behind the MXU) vs fully serial traffic. The
  # bench shape is compute-dominant, so the SERIAL bound is the
  # informative one — it is what the fusions move, by deleting traffic
  return {
      "flops_per_step": fl,
      "hbm_bytes_per_step": int(bytes_total),
      "t_compute_ms": round(t_compute * 1e3, 3),
      "t_memory_ms": round(t_memory * 1e3, 3),
      "bound": "memory" if t_memory > t_compute else "compute",
      "mfu_overlapped": round(t_useful / max(t_compute, t_memory), 4),
      "mfu_serial": round(t_useful / (t_compute + t_memory), 4),
      "tok_s_serial": round(B * S / (t_compute + t_memory), 1),
  }


def serving_analyze(gen, hbm_gbps, batch, context, kv_heads, cache_bytes):
  """Decode-step roofline: one token per sequence per step.

  Traffic per step = ONE full weight read (shared across the batch —
  the dominant term at small batch/context) + the per-sequence KV-cache
  read (B × C × hk × d × 2 arrays; the term GQA divides by H/hk and
  int8 halves vs bf16, plus its C×hk f32 scales). FLOPs per step =
  2N per token + the attention dots (4·C·D per token per layer at full
  query-head compute — grouping shrinks cache BYTES, not FLOPs).
  """
  from tensorflowonspark_tpu.utils import profiler
  head_d = D // H
  N = n_params(kv_heads)
  weight_bytes = N * BF16                      # serving weights in bf16
  cache_bytes_step = batch * context * kv_heads * head_d * 2 * cache_bytes
  if cache_bytes < BF16:                       # int8: + per-token scales
    cache_bytes_step += batch * context * kv_heads * 2 * F32
  fl = batch * (2 * N + 4 * context * D * L)
  peak = profiler.PEAK_BF16_FLOPS[gen]
  t_comp = fl / peak
  t_mem = (weight_bytes + cache_bytes_step) / (hbm_gbps * 1e9)
  t = max(t_comp, t_mem)
  # context where the cache read overtakes the weight read — below it,
  # shrinking the cache cannot move the ceiling
  c_star = weight_bytes / (batch * kv_heads * head_d * 2 * cache_bytes)
  return {
      "weight_mb_per_step": round(weight_bytes / 1e6, 1),
      "cache_mb_per_step": round(cache_bytes_step / 1e6, 1),
      "bound": "memory" if t_mem > t_comp else "compute",
      "decode_tok_s_ceiling": round(batch / t, 1),
      "context_crossover": int(c_star),
  }


SERVING_CONFIGS = [
    ("mha_bf16", H, 2), ("gqa4_bf16", 4, 2), ("mqa_bf16", 1, 2),
    ("mha_int8", H, 1), ("gqa4_int8", 4, 1), ("mqa_int8", 1, 1),
]


def serving_main(args, hbm):
  rows = []
  for name, kv, cb in SERVING_CONFIGS:
    r = serving_analyze(args.gen, hbm, args.batch, args.context, kv, cb)
    r["config"] = name
    rows.append(r)
    print(json.dumps(r))
  sys.stderr.write(
      "\nDecode ceilings @ batch=%d context=%d (%s): per-step traffic = "
      "one weight read + the KV-cache read; below context~crossover the "
      "weight read dominates and cache levers cannot move the ceiling\n"
      "| config | weights MB | cache MB | bound | tok/s ceiling | "
      "crossover C |\n|---|---|---|---|---|---|\n"
      % (args.batch, args.context, args.gen))
  for r in rows:
    sys.stderr.write("| %s | %.0f | %.1f | %s | %.0f | %d |\n"
                     % (r["config"], r["weight_mb_per_step"],
                        r["cache_mb_per_step"], r["bound"],
                        r["decode_tok_s_ceiling"], r["context_crossover"]))


CONFIGS = [
    ("base", {}),
    ("lnmm_fuseqkv", {"ln_matmul_impl": "fused", "fuse_qkv": True}),
    ("actmm", {"act_matmul_impl": "fused"}),
    ("allfused", {"ln_matmul_impl": "fused", "fuse_qkv": True,
                  "act_matmul_impl": "fused"}),
    ("gqa4", {"num_kv_heads": 4}),
    ("gqa4_allfused", {"num_kv_heads": 4, "ln_matmul_impl": "fused",
                       "fuse_qkv": True, "act_matmul_impl": "fused"}),
    ("rematdots_b16", {"remat": "dots"}),
    ("rematfull_b16", {"remat": "none"}),
]


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument("--gen", default="v5e", choices=sorted(HBM_GBPS))
  ap.add_argument("--hbm-gbps", type=float, default=None)
  ap.add_argument("--serving", action="store_true",
                  help="decode-step ceilings (weight read vs KV-cache "
                       "read) instead of the training-step analysis")
  ap.add_argument("--batch", type=int, default=8)
  ap.add_argument("--context", type=int, default=2048)
  args = ap.parse_args()
  hbm = args.hbm_gbps or HBM_GBPS[args.gen]
  if args.serving:
    return serving_main(args, hbm)

  rows = []
  for name, cfg in CONFIGS:
    r = analyze(cfg, args.gen, hbm)
    r["config"] = name
    rows.append(r)
    print(json.dumps(r))
  sys.stderr.write("\n| config | t_comp ms | t_mem ms | MFU serial→"
                   "overlapped | tok/s (serial) |\n|---|---|---|---|---|\n")
  for r in rows:
    sys.stderr.write("| %s | %.2f | %.2f | %.1f%% → %.1f%% | %.0f |\n"
                     % (r["config"], r["t_compute_ms"], r["t_memory_ms"],
                        100 * r["mfu_serial"], 100 * r["mfu_overlapped"],
                        r["tok_s_serial"]))


if __name__ == "__main__":
  main()
