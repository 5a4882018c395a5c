"""Validate + time the Pallas kernels on a REAL TPU chip (interpret=False).

Round-1 verdict flagged that every Pallas kernel had only ever executed in
``interpret=True`` mode on CPU, so real Mosaic lowering (block shapes, lane
tiling, 1-D iota, scalar blocks) was unproven. This harness runs each kernel
on the real chip, checks numerics against the dense XLA reference, and times
both — it is the evidence artifact for "the production code path works".

Usage:  python tools/tpu_validate.py            # full matrix
        python tools/tpu_validate.py --quick    # one shape per kernel
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _timeit(fn, *args, warmup=2, iters=10):
  import jax
  for _ in range(warmup):
    out = fn(*args)
  jax.block_until_ready(out)
  t0 = time.perf_counter()
  for _ in range(iters):
    out = fn(*args)
  jax.block_until_ready(out)
  return (time.perf_counter() - t0) / iters


def _dense_attn(q, k, v, causal):
  import jax.numpy as jnp
  d = q.shape[-1]
  s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                 k.astype(jnp.float32)) / (d ** 0.5)
  if causal:
    sq, sk = s.shape[-2], s.shape[-1]
    mask = np.tril(np.ones((sq, sk), bool), k=sk - sq)
    s = jnp.where(mask, s, -1e30)
  p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
  p = p / jnp.sum(p, axis=-1, keepdims=True)
  return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


def check_flash(results, shapes, dtype_name):
  import contextlib
  import jax
  import jax.numpy as jnp
  import importlib
  fa = importlib.import_module('tensorflowonspark_tpu.ops.flash_attention')

  dtype = dict(bf16=jnp.bfloat16, f32=jnp.float32)[dtype_name]
  # f32 runs under precision=highest so it is validated at f32 accuracy —
  # at the MXU's default precision (bf16 mantissa passes for any input
  # dtype) a bf16-grade tolerance would make the f32 rows redundant
  prec = (jax.default_matmul_precision("highest") if dtype_name == "f32"
          else contextlib.nullcontext())
  for (b, s, h, d, causal) in shapes:
    key = jax.random.PRNGKey(0)
    kq, kk, kv, kg = jax.random.split(key, 4)
    q = jax.random.normal(kq, (b, s, h, d), dtype)
    k = jax.random.normal(kk, (b, s, h, d), dtype)
    v = jax.random.normal(kv, (b, s, h, d), dtype)
    g = jax.random.normal(kg, (b, s, h, d), dtype)

    flash = jax.jit(lambda q, k, v: fa.flash_attention(q, k, v, causal=causal))
    dense = jax.jit(lambda q, k, v: _dense_attn(q, k, v, causal))
    name = "flash_fwd[%s b%d s%d h%d d%d %s]" % (
        dtype_name, b, s, h, d, "causal" if causal else "full")
    try:
      with prec:
        out_f = flash(q, k, v)
        out_d = dense(q, k, v)
      err = float(jnp.max(jnp.abs(out_f.astype(jnp.float32) -
                                  out_d.astype(jnp.float32))))
      tol = 2e-2 if dtype_name == "bf16" else 2e-5
      t_f = _timeit(flash, q, k, v)
      t_d = _timeit(dense, q, k, v)
      results.append(dict(kernel=name, ok=err < tol, max_err=err,
                          flash_ms=round(t_f * 1e3, 3),
                          dense_ms=round(t_d * 1e3, 3),
                          speedup=round(t_d / t_f, 2)))
    except Exception as e:  # noqa: BLE001 - record, keep going
      results.append(dict(kernel=name, ok=False,
                          error=repr(e)[:400]))
      continue

    # backward — both kernel plans (fused single-pass is the default;
    # split two-kernel is what it falls back to where it does not fit)
    base = name.replace("fwd", "bwd")
    # the dense reference gradient is mode-independent: compute/time once
    try:
      loss_d = jax.jit(jax.grad(
          lambda q, k, v: jnp.sum(
              _dense_attn(q, k, v, causal)
              .astype(jnp.float32) * g.astype(jnp.float32)),
          argnums=(0, 1, 2)))
      with prec:
        gd = loss_d(q, k, v)
      t_d = _timeit(loss_d, q, k, v)
    except Exception as e:  # noqa: BLE001
      results.append(dict(kernel=base + "{dense-ref}", ok=False,
                          error=repr(e)[:400]))
      continue
    for bwd_mode in ("fused", "split"):
      name = "%s{%s}" % (base, bwd_mode)
      try:
        loss_f = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(
                fa.flash_attention(q, k, v, causal=causal, bwd=bwd_mode)
                .astype(jnp.float32) * g.astype(jnp.float32)),
            argnums=(0, 1, 2)))
        with prec:
          gf = loss_f(q, k, v)
        err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                        b_.astype(jnp.float32))))
                  for a, b_ in zip(gf, gd))
        tol = 1e-1 if dtype_name == "bf16" else 1e-3
        t_f = _timeit(loss_f, q, k, v)
        results.append(dict(kernel=name, ok=err < tol, max_err=err,
                            flash_ms=round(t_f * 1e3, 3),
                            dense_ms=round(t_d * 1e3, 3),
                            speedup=round(t_d / t_f, 2)))
      except Exception as e:  # noqa: BLE001
        results.append(dict(kernel=name, ok=False, error=repr(e)[:400]))


def check_flash_gqa(results, shapes):
  """Grouped-query attention through the native grouped kernels: K/V
  carry h/g heads and are consumed UNEXPANDED (grouped-aware KV BlockSpec
  in fwd/dQ; cross-head dK/dV grid accumulation in both backward plans).
  Reference = dense attention over explicitly expanded K/V; grouped dK/dV
  are compared against AD through that expand (which sums each group)."""
  import jax
  import jax.numpy as jnp
  import importlib
  fa = importlib.import_module('tensorflowonspark_tpu.ops.flash_attention')

  for (b, s, h, hk, d, causal) in shapes:
    key = jax.random.PRNGKey(4)
    kq, kk, kv, kg = jax.random.split(key, 4)
    q = jax.random.normal(kq, (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, s, hk, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, s, hk, d), jnp.bfloat16)
    g = jax.random.normal(kg, (b, s, h, d), jnp.bfloat16)
    rep = lambda t: jnp.repeat(t, h // hk, axis=2)  # noqa: E731

    flash = jax.jit(lambda q, k, v: fa.flash_attention(q, k, v,
                                                       causal=causal))
    dense = jax.jit(lambda q, k, v: _dense_attn(q, rep(k), rep(v), causal))
    name = "flash_gqa_fwd[bf16 b%d s%d h%d hk%d d%d %s]" % (
        b, s, h, hk, d, "causal" if causal else "full")
    try:
      out_f = flash(q, k, v)
      out_d = dense(q, k, v)
      err = float(jnp.max(jnp.abs(out_f.astype(jnp.float32) -
                                  out_d.astype(jnp.float32))))
      t_f = _timeit(flash, q, k, v)
      t_d = _timeit(dense, q, k, v)
      results.append(dict(kernel=name, ok=err < 2e-2, max_err=err,
                          flash_ms=round(t_f * 1e3, 3),
                          dense_ms=round(t_d * 1e3, 3),
                          speedup=round(t_d / t_f, 2)))
    except Exception as e:  # noqa: BLE001 - record, keep going
      results.append(dict(kernel=name, ok=False, error=repr(e)[:400]))
      continue

    base = name.replace("fwd", "bwd")
    try:
      loss_d = jax.jit(jax.grad(
          lambda q, k, v: jnp.sum(
              _dense_attn(q, rep(k), rep(v), causal)
              .astype(jnp.float32) * g.astype(jnp.float32)),
          argnums=(0, 1, 2)))
      gd = loss_d(q, k, v)
      t_d = _timeit(loss_d, q, k, v)
    except Exception as e:  # noqa: BLE001
      results.append(dict(kernel=base + "{dense-ref}", ok=False,
                          error=repr(e)[:400]))
      continue
    for bwd_mode in ("fused", "split"):
      name = "%s{%s}" % (base, bwd_mode)
      try:
        loss_f = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(
                fa.flash_attention(q, k, v, causal=causal, bwd=bwd_mode)
                .astype(jnp.float32) * g.astype(jnp.float32)),
            argnums=(0, 1, 2)))
        gf = loss_f(q, k, v)
        err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                        b_.astype(jnp.float32))))
                  for a, b_ in zip(gf, gd))
        t_f = _timeit(loss_f, q, k, v)
        results.append(dict(kernel=name, ok=err < 1e-1, max_err=err,
                            flash_ms=round(t_f * 1e3, 3),
                            dense_ms=round(t_d * 1e3, 3),
                            speedup=round(t_d / t_f, 2)))
      except Exception as e:  # noqa: BLE001
        results.append(dict(kernel=name, ok=False, error=repr(e)[:400]))


def check_flash_block(results):
  """flash_attention_block with TRACED position bases + merge_partials.

  This is the ring-attention production path: bases reach the kernel
  through SMEM scalar prefetch as runtime values (inside shard_map they
  come from ``lax.axis_index``), and the causal-skip loop bounds become
  data-dependent while-loop trip counts. Computing full causal attention
  as two merged KV-half partials exercises exactly that, single-chip.
  """
  import jax
  import jax.numpy as jnp
  import importlib
  fa = importlib.import_module('tensorflowonspark_tpu.ops.flash_attention')

  b, s, h, d = 2, 1024, 4, 64
  key = jax.random.PRNGKey(2)
  kq, kk, kv = jax.random.split(key, 3)
  q = jax.random.normal(kq, (b, s, h, d), jnp.bfloat16)
  k = jax.random.normal(kk, (b, s, h, d), jnp.bfloat16)
  v = jax.random.normal(kv, (b, s, h, d), jnp.bfloat16)
  half = s // 2

  @jax.jit
  def two_block(q, k, v, kv_base0, kv_base1):
    # bases enter as traced device scalars, like lax.axis_index would
    o0, l0 = fa.flash_attention_block(q, k[:, :half], v[:, :half],
                                      0, kv_base0, causal=True)
    o1, l1 = fa.flash_attention_block(q, k[:, half:], v[:, half:],
                                      0, kv_base1, causal=True)
    o, _ = fa.merge_partials(o0, l0, o1, l1)
    return o

  name = "flash_block_traced_bases[bf16 b%d s%d h%d d%d]" % (b, s, h, d)
  try:
    out = two_block(q, k, v, jnp.int32(0), jnp.int32(half))
    ref = _dense_attn(q, k, v, True)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) -
                                ref.astype(jnp.float32))))
    t = _timeit(two_block, q, k, v, jnp.int32(0), jnp.int32(half))
    results.append(dict(kernel=name, ok=err < 2e-2, max_err=err,
                        flash_ms=round(t * 1e3, 3)))
  except Exception as e:  # noqa: BLE001
    results.append(dict(kernel=name, ok=False, error=repr(e)[:400]))

  # gradient through both partials and the merge (ring bwd path)
  name = "flash_block_traced_bases_grad"
  try:
    g = jax.random.normal(jax.random.PRNGKey(3), (b, s, h, d), jnp.bfloat16)
    gfn = jax.jit(jax.grad(
        lambda q, k, v, b0, b1: jnp.sum(
            two_block.__wrapped__(q, k, v, b0, b1).astype(jnp.float32) *
            g.astype(jnp.float32)), argnums=(0, 1, 2)))
    gref = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(
            _dense_attn(q, k, v, True).astype(jnp.float32) *
            g.astype(jnp.float32)), argnums=(0, 1, 2)))
    gb = gfn(q, k, v, jnp.int32(0), jnp.int32(half))
    gr = gref(q, k, v)
    err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                    b_.astype(jnp.float32))))
              for a, b_ in zip(gb, gr))
    results.append(dict(kernel=name, ok=err < 1e-1, max_err=err))
  except Exception as e:  # noqa: BLE001
    results.append(dict(kernel=name, ok=False, error=repr(e)[:400]))


def check_layer_norm(results, shapes):
  import jax
  import jax.numpy as jnp
  import importlib
  ln = importlib.import_module('tensorflowonspark_tpu.ops.layer_norm')

  for (rows, d), dtype_name in [(s, dt) for s in shapes
                                for dt in ("f32", "bf16")]:
    dtype = dict(bf16=jnp.bfloat16, f32=jnp.float32)[dtype_name]
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (rows, d), dtype)
    gamma = (jnp.ones((d,), dtype) * 1.1).astype(dtype)
    tol = 2e-2 if dtype_name == "bf16" else 1e-4

    fused = jax.jit(lambda x, g: ln.layer_norm(x, g))
    ref = jax.jit(lambda x, g: (
        ((x.astype(jnp.float32) -
          jnp.mean(x.astype(jnp.float32), -1, keepdims=True)) *
         jax.lax.rsqrt(jnp.var(x.astype(jnp.float32), -1, keepdims=True)
                       + 1e-6) * g.astype(jnp.float32)).astype(x.dtype)))
    name = "layer_norm[%s %dx%d]" % (dtype_name, rows, d)
    try:
      err = float(jnp.max(jnp.abs(fused(x, gamma).astype(jnp.float32) -
                                  ref(x, gamma).astype(jnp.float32))))
      t_f = _timeit(fused, x, gamma)
      t_r = _timeit(ref, x, gamma)
      results.append(dict(kernel=name, ok=err < tol, max_err=err,
                          fused_ms=round(t_f * 1e3, 3),
                          xla_ms=round(t_r * 1e3, 3),
                          speedup=round(t_r / t_f, 2)))
    except Exception as e:  # noqa: BLE001
      results.append(dict(kernel=name, ok=False, error=repr(e)[:400]))

    # gradient path (used by FusedLayerNorm during training)
    name = "layer_norm_grad[%s %dx%d]" % (dtype_name, rows, d)
    try:
      gf = jax.jit(jax.grad(
          lambda x, g: jnp.sum(ln.layer_norm(x, g).astype(jnp.float32)),
          argnums=(0, 1)))
      gr = jax.jit(jax.grad(
          lambda x, g: jnp.sum(ref.__wrapped__(x, g).astype(jnp.float32)),
          argnums=(0, 1)))
      err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                      b_.astype(jnp.float32))))
                for a, b_ in zip(gf(x, gamma), gr(x, gamma)))
      results.append(dict(kernel=name, ok=err < max(tol, 1e-3), max_err=err))
    except Exception as e:  # noqa: BLE001
      results.append(dict(kernel=name, ok=False, error=repr(e)[:400]))


# The sweep's shapes and tile grids — module-level so the deviceless gate
# (tools/mosaic_gate.py --tile-sweep) compile-validates EXACTLY the tiles
# this sweep will time on-chip; retune them here and the gate follows.
SWEEP_ATTN_SHAPE = (2, 1024, 8, 64)          # bench-class b, s, h, d
SWEEP_FLASH_GRID = [(128, 256), (128, 512), (256, 256), (256, 512),
                    (256, 1024), (512, 512)]


def sweep_blocks(results):
  """Auto-tune kernel tile sizes at the bench shapes (``--sweep-blocks``).

  Round 2 found DEFAULT_BWD_BLOCKS by manual probing during the one
  window the chip answered; this automates it so a single chip session
  yields the full tuning surface: flash forward and both backward plans
  over a (blk_q, blk_k) grid. Emits one row per timed point plus a
  ``*_best`` row per kernel — apply the winners to the kernel defaults
  only when they beat the current ones.
  """
  import importlib
  import jax
  import jax.numpy as jnp
  fa = importlib.import_module('tensorflowonspark_tpu.ops.flash_attention')

  b, s, h, d = SWEEP_ATTN_SHAPE
  key = jax.random.PRNGKey(7)
  kq, kk, kv, kg = jax.random.split(key, 4)
  q = jax.random.normal(kq, (b, s, h, d), jnp.bfloat16)
  k = jax.random.normal(kk, (b, s, h, d), jnp.bfloat16)
  v = jax.random.normal(kv, (b, s, h, d), jnp.bfloat16)
  g = jax.random.normal(kg, (b, s, h, d), jnp.bfloat16)

  grid = SWEEP_FLASH_GRID
  best = {}
  for blk_q, blk_k in grid:
    name = "flash_fwd_blocks[%dx%d]" % (blk_q, blk_k)
    try:
      fn = jax.jit(lambda q, k, v, bq=blk_q, bk=blk_k: fa.flash_attention(
          q, k, v, causal=True, blk_q=bq, blk_k=bk))
      t = _timeit(fn, q, k, v)
      results.append(dict(kernel=name, ok=True, sweep=True,
                          ms=round(t * 1e3, 3)))
      if t < best.get("flash_fwd", (1e9,))[0]:
        best["flash_fwd"] = (t, (blk_q, blk_k))
    except Exception as e:  # noqa: BLE001 - record, keep going
      results.append(dict(kernel=name, ok=False, sweep=True,
                          error=repr(e)[:200]))
    for bwd_mode in ("fused", "split"):
      name = "flash_bwd_%s_blocks[%dx%d]" % (bwd_mode, blk_q, blk_k)
      try:
        fn = jax.jit(jax.grad(
            lambda q, k, v, bq=blk_q, bk=blk_k, bm=bwd_mode: jnp.sum(
                fa.flash_attention(q, k, v, causal=True, bwd=bm,
                                   blk_bwd_q=bq, blk_bwd_k=bk)
                .astype(jnp.float32) * g.astype(jnp.float32)),
            argnums=(0, 1, 2)))
        t = _timeit(fn, q, k, v)
        results.append(dict(kernel=name, ok=True, sweep=True,
                            ms=round(t * 1e3, 3)))
        kb = "flash_bwd_%s" % bwd_mode
        if t < best.get(kb, (1e9,))[0]:
          best[kb] = (t, (blk_q, blk_k))
      except Exception as e:  # noqa: BLE001
        results.append(dict(kernel=name, ok=False, sweep=True,
                            error=repr(e)[:200]))

  for kernel, (t, blocks) in sorted(best.items()):
    results.append(dict(kernel="%s_best" % kernel, ok=True, sweep=True,
                        ms=round(t * 1e3, 3), blocks=list(blocks)))


class _TeeResults(list):
  """Write-through results list: each appended row also lands on disk
  immediately (one JSON line), so a run that dies mid-matrix keeps every
  row that finished instead of losing the whole run."""

  def __init__(self, path):
    super().__init__()
    self._path = path

  def append(self, row):
    super().append(row)
    if self._path:
      with open(self._path, "a") as f:
        f.write(json.dumps(row) + "\n")


def main(argv=None):
  ap = argparse.ArgumentParser()
  ap.add_argument("--quick", action="store_true")
  ap.add_argument("--json", default=None, help="write results to this file")
  ap.add_argument("--sweep-blocks", action="store_true",
                  help="also auto-tune kernel tile sizes at the bench "
                       "shapes (flash fwd/bwd)")
  ap.add_argument("--sweep-only", action="store_true",
                  help="run ONLY the block sweep (skip the validation "
                       "matrix — e.g. when a capture just ran it)")
  ap.add_argument("--select", default=None,
                  help="comma list of family[:shape_idx] items to run "
                       "instead of the full matrix. Families: "
                       "flash_bf16, flash_f32, gqa, block, ln")
  ap.add_argument("--append-jsonl", default=None,
                  help="append each result row to this file the moment it "
                       "is produced (survives a mid-run chip drop)")
  args = ap.parse_args(argv)

  import jax
  dev = jax.devices()[0]
  print("device: %s (%s)" % (dev, dev.platform), file=sys.stderr)
  if dev.platform != "tpu":
    # on-chip numerics + timing: another backend's numbers are not this
    # tool's result, and must not be mistaken for it
    print("not a TPU: JAX found the %s backend (%s); tpu_validate runs on "
          "the chip or not at all" % (dev.platform, dev.device_kind),
          file=sys.stderr)
    return 3

  results = _TeeResults(args.append_jsonl)
  if args.quick:
    flash_shapes = [(1, 512, 4, 64, True)]
    gqa_shapes = [(2, 1024, 8, 2, 64, True)]
    ln_shapes = [(4096, 1024)]
  else:
    flash_shapes = [
        (1, 512, 4, 64, True),
        (2, 1024, 8, 64, True),
        (2, 1024, 8, 64, False),
        (1, 2048, 8, 128, True),
        (4, 4096, 8, 128, True),
    ]
    # (b, s, h, hk, d, causal): group-of-4, MQA, and a long-context shape
    # past the fused plan's VMEM budget (exercises the split fallback)
    gqa_shapes = [
        (2, 1024, 8, 2, 64, True),
        (2, 1024, 8, 1, 64, True),
        (1, 4096, 8, 2, 128, True),
    ]
    ln_shapes = [(4096, 1024), (8192, 768), (16384, 4096)]

  families = {
      "flash_bf16": (flash_shapes, lambda sh: check_flash(results, sh,
                                                          "bf16")),
      "flash_f32": (flash_shapes, lambda sh: check_flash(results, sh,
                                                         "f32")),
      "gqa": (gqa_shapes, lambda sh: check_flash_gqa(results, sh)),
      "block": (None, lambda sh: check_flash_block(results)),
      "ln": (ln_shapes, lambda sh: check_layer_norm(results, sh)),
  }
  if args.select:
    for spec in args.select.split(","):
      fam, _, idx = spec.strip().partition(":")
      if fam not in families:
        print("unknown --select family %r; valid: %s"
              % (fam, sorted(families)), file=sys.stderr)
        return 2
      shapes, runner = families[fam]
      if idx and shapes is not None and not 0 <= int(idx) < len(shapes):
        print("--select %s: shape index out of range (family has %d "
              "shapes%s)" % (spec, len(shapes),
                             "; note --quick shrinks the lists"
                             if args.quick else ""), file=sys.stderr)
        return 2
      if shapes is None:
        runner(None)
      elif idx:
        runner([shapes[int(idx)]])
      else:
        runner(shapes)
  elif not args.sweep_only:
    for dt in (("bf16",) if args.quick else ("bf16", "f32")):
      check_flash(results, flash_shapes, dt)
    check_flash_gqa(results, gqa_shapes)
    check_flash_block(results)
    check_layer_norm(results, ln_shapes)
  if args.sweep_blocks or (args.sweep_only and not args.select):
    sweep_blocks(results)

  # pass/fail counts only the VALIDATION rows: sweep rows are timing
  # probes whose grid deliberately includes infeasible points (VMEM
  # overflows), and must not flip the exit code or the ok-summary
  checks = [r for r in results if not r.get("sweep")]
  n_ok = sum(1 for r in checks if r.get("ok"))
  for r in results:
    print(json.dumps(r))
  print("\n%d/%d kernels ok (+%d sweep rows)"
        % (n_ok, len(checks), len(results) - len(checks)), file=sys.stderr)
  if args.json:
    with open(args.json, "w") as f:
      json.dump(dict(device=str(dev), results=results), f, indent=1)
  if checks:
    return 0 if n_ok == len(checks) else 1
  # sweep-only: success means the sweep produced usable tuning data —
  # an all-failed sweep (chip dropped mid-run) must not read as healthy
  return 0 if any(r.get("ok") for r in results) else 1


if __name__ == "__main__":
  sys.exit(main())
