"""Benchmark: ResNet-50 + Transformer training throughput on one chip, bf16.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra",
"platform", "device_kind", "device_count"}. The reference publishes no
quantitative numbers (BASELINE.md — its claims are qualitative), so
vs_baseline is reported against a fixed ENGINEERING TARGET of 1000
images/sec/chip for ResNet-50@224 in bf16 (the "target" note in the JSON
marks it as such). `extra` carries the Transformer training numbers:
tokens/sec and model-flops-utilization (MFU) against the chip generation's
bf16 peak (one table: obs/profiler.PEAK_BF16_FLOPS).

Runs single-process on the TPU JAX exposes, and FAILS on a machine without
one: a CPU timing is not a device metric. ``TOS_BENCH_SMOKE=1`` is the one
exception — tiny shapes so CI can drive the code path on the CPU; its JSON
still names the device it ran on, and carries no MFU. A watchdog guards the
run so a wedged device runtime still yields a JSON line (and exit 2).

The persistent compile cache is placed by ``utils.compile_cache``: where
``JAX_COMPILATION_CACHE_DIR`` says, else ``<repo>/.jax_cache``.
"""

import json
import os
import sys
import time

_T_BENCH_START = time.time()   # zero point for the stage-timestamp logs

TARGET_IMG_PER_SEC = 1000.0   # engineering target, not a reference number
BATCH = 128
IMAGE = (224, 224, 3)
MEASURE = 10   # steps chained per timed dispatch

# Transformer benchmark shape: GPT-2-small-class decoder (124M params).
# batch 16 without remat is the single-chip throughput sweet spot on v5e
# (batch 8: 83k tok/s; batch 16: 88k; batch 24+ OOMs without remat; remat
# costs ~21% at batch 16) — remat stays available for memory-bound configs.
TFM_LAYERS, TFM_DMODEL, TFM_HEADS, TFM_DFF = 12, 768, 12, 3072
TFM_VOCAB, TFM_SEQ, TFM_BATCH = 32000, 1024, 16
TFM_REMAT = False
TFM_MEASURE = 8

if os.environ.get("TOS_BENCH_SMOKE"):
  # tiny shapes so CI can drive the full bench path on CPU
  BATCH, IMAGE, MEASURE = 8, (64, 64, 3), 3
  TFM_LAYERS, TFM_DMODEL, TFM_HEADS, TFM_DFF = 2, 128, 4, 256
  TFM_VOCAB, TFM_SEQ, TFM_BATCH = 512, 128, 2
  TFM_MEASURE = 3


def _steps_per_sec(step_fn, state, args, k, label):
  """Per-step time via a lax.scan-chained K-step dispatch.

  Chains K steps inside ONE jitted scan and subtracts a 1-step baseline,
  so per-dispatch host overhead and the loss fetch drop out of the
  quotient. (ROADMAP S1 revisits this arithmetic against a device trace.)
  """
  import functools
  import time as _time   # deferred with jax: bench imports nothing heavy at module load
  import jax
  from jax import lax

  @functools.partial(jax.jit, static_argnames=("k",))
  def multi(state, k):
    def body(st, _):
      st, loss = step_fn(st, *args)
      return st, loss
    st, losses = lax.scan(body, state, None, length=k)
    return st, losses[-1]

  # compile and execute are staged separately, each logged with a
  # timestamp, so a watchdog fire says WHICH stage the runtime wedged in
  t_compile = _time.time()
  sys.stderr.write("%s lower+compile 1-step start t=%.1fs\n"
                   % (label, t_compile - _T_BENCH_START))
  sys.stderr.flush()
  c1 = multi.lower(state, 1).compile()
  sys.stderr.write("%s 1-step compiled %.1fs\n"
                   % (label, _time.time() - t_compile))
  sys.stderr.flush()
  t_ck = _time.time()
  ck = multi.lower(state, k).compile()
  sys.stderr.write("%s %d-step compiled %.1fs\n"
                   % (label, k, _time.time() - t_ck))
  sys.stderr.flush()
  t_exec = _time.time()
  _, loss = c1(state)
  first_loss = float(loss)   # full fetch = real sync
  t_c1 = _time.time() - t_exec
  t_ck = _time.time()
  _, loss = ck(state)
  float(loss)
  sys.stderr.write("%s first dispatch (1-step %.1fs + %d-step %.1fs) "
                   "loss=%.3f\n"
                   % (label, t_c1, k, _time.time() - t_ck, first_loss))
  sys.stderr.flush()

  def _timed(c):
    t0 = _time.time()
    _, loss = c(state)
    float(loss)
    return _time.time() - t0

  # best-of-2 each, and guard the difference: where dt_k - dt_1 is within
  # noise fall back to the plain K-run average (a conservative
  # under-estimate) rather than divide by <= 0
  dt_k = min(_timed(ck), _timed(ck))
  dt_1 = min(_timed(c1), _timed(c1))
  if dt_k - dt_1 <= 0.2 * dt_k:
    return k / dt_k
  return (k - 1) / (dt_k - dt_1)


#: the device this run measured on, as JAX reports it — set once by main()
#: and written into EVERY JSON line (None until the backend is up)
_DEVICE = {"platform": None, "device_kind": None, "device_count": None}


def _emit(value, unit="images/sec/chip", metric="resnet50_train_throughput",
          note=None, extra=None):
  line = {"metric": metric, "value": round(float(value), 2), "unit": unit,
          "vs_baseline": round(float(value) / TARGET_IMG_PER_SEC, 3),
          "target": "%g images/sec/chip is an engineering target; the "
                    "reference publishes no numbers" % TARGET_IMG_PER_SEC}
  line.update(_DEVICE)
  if note:
    line["note"] = note
  if extra:
    line["extra"] = extra
  print(json.dumps(line))
  # the watchdog thread follows with os._exit, which skips stdio
  # flushing — under a pipe the buffered JSON line would be silently lost
  sys.stdout.flush()


def _bench_resnet():
  import numpy as np
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import resnet

  model = resnet.ResNet50(num_classes=1000)
  state = resnet.create_state(jax.random.PRNGKey(0), model,
                              image_shape=IMAGE)
  rng = np.random.RandomState(0)
  images = jnp.asarray(rng.rand(BATCH, *IMAGE), jnp.float32)
  labels = jnp.asarray(rng.randint(0, 1000, BATCH), jnp.int32)

  steps_per_sec = _steps_per_sec(resnet.train_step, state,
                                 (images, labels), MEASURE, "resnet")
  return BATCH * steps_per_sec


def _chip_peak_flops():
  """(generation, bf16_peak) from the ONE peak table, keyed through the
  device_kind JAX reports. An unknown TPU raises (never an assumed peak);
  off the TPU (TOS_BENCH_SMOKE on the CPU) there is no peak and no MFU."""
  import jax
  from tensorflowonspark_tpu.obs import profiler
  dev = jax.devices()[0]
  if dev.platform != "tpu":
    return None, None
  return profiler.chip_peak_bf16_flops(dev.device_kind)


def _mfu(flops_per_token, tokens_per_sec, peak):
  from tensorflowonspark_tpu.obs import profiler
  if peak is None:
    return None   # not measured: no TPU under this run
  return round(profiler.mfu(flops_per_token, tokens_per_sec, peak), 4)


def _bench_transformer(batch=None, seq=None, loss_impl="full",
                       **cfg_overrides):
  """Decoder-only LM training: tokens/sec + MFU on one chip."""
  import numpy as np
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import transformer as tfm

  batch = TFM_BATCH if batch is None else batch
  seq = TFM_SEQ if seq is None else seq
  cfg_overrides.setdefault("remat", TFM_REMAT)
  cfg = tfm.TransformerConfig(
      vocab_size=TFM_VOCAB, num_layers=TFM_LAYERS, num_heads=TFM_HEADS,
      d_model=TFM_DMODEL, d_ff=TFM_DFF, max_seq_len=seq,
      **cfg_overrides)
  state = tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=seq)
  n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(state.params))

  def train_step(state, tokens):
    def loss_fn(params):
      if loss_impl == "blocked":
        # fused projection+xent: peak memory is [B, chunk, V], not
        # [B, S, V] — this is what bounds the trainable batch size
        hidden = state.apply_fn({"params": params}, tokens,
                                return_hidden=True)
        return tfm.causal_lm_loss_blocked(
            hidden, tfm.tied_embedding_table(params), tokens)
      logits = state.apply_fn({"params": params}, tokens)
      return tfm.causal_lm_loss(logits, tokens)
    loss, grads = jax.value_and_grad(loss_fn)(state.params)
    return state.apply_gradients(grads=grads), loss

  rng = np.random.RandomState(0)
  tokens = jnp.asarray(rng.randint(0, TFM_VOCAB, (batch, seq)),
                       jnp.int32)

  steps_per_sec = _steps_per_sec(train_step, state, (tokens,),
                                 TFM_MEASURE, "transformer")

  from tensorflowonspark_tpu.obs import profiler
  tokens_per_sec = batch * seq * steps_per_sec
  flops_per_token = profiler.transformer_flops_per_token(
      n_params, TFM_LAYERS, TFM_DMODEL, seq)
  gen, peak = _chip_peak_flops()
  return {"transformer_tokens_per_sec": round(tokens_per_sec, 1),
          "transformer_mfu": _mfu(flops_per_token, tokens_per_sec, peak),
          "transformer_params": n_params,
          "chip_generation": gen,
          "chip_peak_bf16_flops": peak}


def _bench_long_context():
  """Long-sequence LM training (s=4096, head_dim=128): the config where
  attention dominates the FLOPs and the fused flash kernels (including
  the single-pass backward) carry the step — dense attention at this
  shape materializes [B, H, 4096, 4096] scores and does not fit."""
  import numpy as np
  import jax
  from tensorflowonspark_tpu.models import transformer as tfm
  from tensorflowonspark_tpu.obs import profiler

  layers, d_model, heads, seq, batch = 4, 1024, 8, 4096, 4
  if os.environ.get("TOS_BENCH_SMOKE"):
    layers, d_model, heads, seq, batch = 2, 128, 4, 256, 2
  cfg = tfm.TransformerConfig(
      vocab_size=TFM_VOCAB, num_layers=layers, num_heads=heads,
      d_model=d_model, d_ff=4 * d_model, max_seq_len=seq, remat=False)
  state = tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=seq)
  n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(state.params))

  def train_step(state, tokens):
    def loss_fn(params):
      # blocked loss: at s=4096 the [B, S, V] logits are 2 GB and the
      # fused projection+xent is what makes this config trainable
      hidden = state.apply_fn({"params": params}, tokens,
                              return_hidden=True)
      return tfm.causal_lm_loss_blocked(
          hidden, tfm.tied_embedding_table(params), tokens)
    loss, grads = jax.value_and_grad(loss_fn)(state.params)
    return state.apply_gradients(grads=grads), loss

  import jax.numpy as jnp
  rng = np.random.RandomState(0)
  tokens = jnp.asarray(rng.randint(0, TFM_VOCAB, (batch, seq)), jnp.int32)
  steps_per_sec = _steps_per_sec(train_step, state, (tokens,),
                                 TFM_MEASURE, "long-context")
  tokens_per_sec = batch * seq * steps_per_sec
  flops_per_token = profiler.transformer_flops_per_token(
      n_params, layers, d_model, seq)
  _, peak = _chip_peak_flops()
  return {"long_context_seq_len": seq,
          "long_context_tokens_per_sec": round(tokens_per_sec, 1),
          "long_context_mfu": _mfu(flops_per_token, tokens_per_sec, peak)}


# best-so-far results, so a watchdog fire mid-run still reports whatever
# finished (exit code 2 either way)
_PARTIAL = {"value": 0.0, "extra": None}


# The MFU-hunt candidate configs (round-2 verdict: fused QKV on chip,
# s=2048, fused-vs-flax LayerNorm; round-3/4 add the ln/act fusions, remat
# policies and GQA). Module-level so tools/mosaic_gate.py --bench-sweep can
# compile-validate every candidate against the deviceless TPU topology
# BEFORE a chip is ever claimed — sweep day then measures, not debugs.
SWEEP_CONFIGS = [
    ("b16_s1024_base", {}),
    ("b16_s1024_fuseqkv", {"fuse_qkv": True}),
    ("b16_s1024_flaxln", {"layer_norm_impl": "flax"}),
    ("b16_s1024_lnmm", {"ln_matmul_impl": "fused"}),
    ("b16_s1024_lnmm_fuseqkv", {"ln_matmul_impl": "fused",
                                "fuse_qkv": True}),
    ("b16_s1024_actmm", {"act_matmul_impl": "fused"}),
    # everything fused: ln1+QKV, ln2+up, gelu+down each one kernel
    ("b16_s1024_allfused", {"ln_matmul_impl": "fused", "fuse_qkv": True,
                            "act_matmul_impl": "fused"}),
    ("b8_s2048", {"batch": 8, "seq": 2048}),
    ("b8_s2048_fuseqkv", {"batch": 8, "seq": 2048, "fuse_qkv": True}),
    ("b8_s2048_allfused", {"batch": 8, "seq": 2048,
                           "ln_matmul_impl": "fused", "fuse_qkv": True,
                           "act_matmul_impl": "fused"}),
    # selective remat: save MXU outputs, recompute elementwise only —
    # batch 24/32 OOM without remat and full remat costs ~21%; "dots"
    # aims at the bigger batch for a fraction of the recompute
    ("b24_s1024_rematdots", {"batch": 24, "remat": True,
                             "remat_policy": "dots"}),
    ("b32_s1024_rematdots", {"batch": 32, "remat": True,
                             "remat_policy": "dots"}),
    ("b32_s1024_rematdots_allfused", {"batch": 32, "remat": True,
                                      "remat_policy": "dots",
                                      "ln_matmul_impl": "fused",
                                      "fuse_qkv": True,
                                      "act_matmul_impl": "fused"}),
    # GQA at the bench shape: 12 query heads on 4 KV heads — the
    # grouped kernels read 3x less KV from HBM; with allfused on top
    ("b16_s1024_gqa4", {"num_kv_heads": 4}),
    ("b16_s1024_gqa4_allfused", {"num_kv_heads": 4,
                                 "ln_matmul_impl": "fused",
                                 "fuse_qkv": True,
                                 "act_matmul_impl": "fused"}),
]


def _sweep():
  """MFU-hunt mode (`TOS_BENCH_SWEEP=1`, manual runs only — the driver
  contract of one JSON line does not apply): measure the transformer bench
  across SWEEP_CONFIGS and print one JSON object with all of them."""
  results = {}
  for name, kw in SWEEP_CONFIGS:
    try:
      r = _bench_transformer(**kw)
      results[name] = {"tok_s": r["transformer_tokens_per_sec"],
                       "mfu": r["transformer_mfu"]}
    except Exception as e:  # noqa: BLE001 - keep sweeping
      results[name] = {"error": str(e)[:200]}
    # a watchdog fire mid-sweep reports every config that finished
    # instead of discarding the round's one capture
    _PARTIAL["extra"] = {"sweep_partial": dict(results)}
    sys.stderr.write("sweep %s: %r\n" % (name, results[name]))
  print(json.dumps({"sweep": results}))


def main():
  import time as _time
  _start_watchdog()
  t_start = _time.time()

  from tensorflowonspark_tpu.utils import compile_cache
  sys.stderr.write("compile cache: %s\n" % compile_cache.setup())
  import jax
  dev = jax.devices()[0]
  _DEVICE.update(platform=dev.platform, device_kind=dev.device_kind,
                 device_count=len(jax.devices()))
  sys.stderr.write("bench devices: %r\n" % (jax.devices(),))
  if dev.platform != "tpu" and not os.environ.get("TOS_BENCH_SMOKE"):
    # no chip, no benchmark: a CPU timing is never a device metric
    _emit(0.0, note="no TPU: JAX found %s (%s); bench.py measures the chip "
                    "or fails (TOS_BENCH_SMOKE=1 drives the code path on "
                    "the CPU at toy shapes)" % (dev.platform,
                                                dev.device_kind))
    return 3

  if os.environ.get("TOS_BENCH_SWEEP"):
    _sweep()
    return 0

  img_per_sec = _bench_resnet()
  _PARTIAL["value"] = img_per_sec
  # a kernel path that fails to lower or run FAILS the bench: measuring
  # the dense/XLA path instead would report a different program
  extra = _bench_transformer()
  _PARTIAL["extra"] = extra
  budget = int(os.environ.get("TOS_BENCH_TIMEOUT", "600"))
  # the fused-kernel config (every Pallas lever on — compile-checked
  # devicelessly, SWEEP_COMPILE.json) measured alongside the base config
  # when there's headroom
  if _time.time() - t_start < budget - 300:
    try:
      fused = _bench_transformer(ln_matmul_impl="fused", fuse_qkv=True,
                                 act_matmul_impl="fused")
      extra["transformer_allfused_tokens_per_sec"] = \
          fused["transformer_tokens_per_sec"]
      extra["transformer_allfused_mfu"] = fused["transformer_mfu"]
      _PARTIAL["extra"] = extra
    except Exception as e:  # noqa: BLE001 - optional extra measurement
      extra["transformer_allfused_error"] = str(e)[:300]
  # optional extra metric — only if there's comfortable headroom before
  # the watchdog would fire and discard the numbers already in hand
  if _time.time() - t_start < budget - 240:
    try:
      extra.update(_bench_long_context())
    except Exception as e:  # noqa: BLE001 - optional extra metric
      extra["long_context_error"] = str(e)[:300]
  else:
    extra["long_context_skipped"] = "insufficient time before watchdog"
  _emit(img_per_sec, extra=extra)
  return 0


def _start_watchdog(timeout_s=None, note=None):
  # watchdog in a TIMER THREAD, not SIGALRM: the device runtime blocks the
  # main thread inside C calls that never return to the bytecode loop, so a
  # signal handler can be deferred indefinitely — a daemon thread calling
  # os._exit always gets through (observed: a wedged compile RPC swallowed
  # the SIGALRM watchdog entirely)
  import threading

  def _watchdog():
    _emit(_PARTIAL["value"], extra=_PARTIAL["extra"],
          note="watchdog: "
               + (note or "device runtime did not respond in time")
               + ("" if not _PARTIAL["value"] else
                  "; value/extra are the partial results that finished"))
    os._exit(2)

  if timeout_s is None:
    timeout_s = int(os.environ.get("TOS_BENCH_TIMEOUT", "600"))
  timer = threading.Timer(timeout_s, _watchdog)
  timer.daemon = True
  timer.start()
  return timer


if __name__ == "__main__":
  try:
    rc = main()
  except Exception as e:  # noqa: BLE001 - the driver needs its JSON line
    _emit(0.0, note="error: %s" % e)
    raise
  sys.exit(rc)
