#!/usr/bin/env python3
"""Builder's reading, never part of a check's run: step zero of the
``keye-vl2-serve-backlog`` cell, timed ALONE on the chip at the cell's shapes.

    chiprun -- python3 benchmarks/tools/index_select_time.py [--tiny] [--widths]

1. the exact selection of 2048 of up to 32768 two ways, at a decode step's
   ``[16, 32768]`` and a prefill chunk's ``[4096, 32768]``:
   ``models.transformer.select_topk`` (a threshold found by 32 passes of
   compare-and-count over the float's bits: the mask directly) and
   ``lax.top_k`` (values and indices; the mask would still need a scatter);
2. the index scores of both shapes (``models.transformer.index_scores``: 16
   heads of 64 against the index leaf);
3. the decode step's read three ways at 16 slots x 32 heads of 128 over 4 KV
   heads, cursors of the cell's mix: the dense branch under the mask
   (``_cached_attention`` with the kernel off), ``ops.decode_attention`` with
   the keep rows, and a gather of the 2048 chosen rows followed by a dense
   read of them;
4. a 4096-token chunk at a cursor of 16384 attending its row of 20480
   positions under the mask: the flash block call with the keep operand in
   blocks of 2048 (what the program runs) and the same blocks as XLA einsums
   under the same mask.

``--widths`` times instead, and alone, the chunk's selection and index scores
over the FIRST 4096, 8192 and 16384 positions of the row, the chunk the last
4096 of them (and over all 32768 again, in the same process): what a
selection that stopped at the chunk's last position would cost, for the
``perf_opt`` PR that weighs it (the program searches the whole row whatever
the cursor).

Prints one JSON line a reading and writes them to
``chiprun_out/index_select_time.json``.  ``--tiny`` is the CPU rehearsal
(small shapes, the kernels in interpret mode): it proves the tool runs, its
numbers mean nothing.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)


def _time(fn, *args, reps: int = 5):
  """Median wall seconds of ``fn(*args)`` (jitted; compiled by a first
  call), each call waited for."""
  import jax
  jax.block_until_ready(fn(*args))
  out = []
  for _ in range(reps):
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    out.append(time.perf_counter() - t0)
  return sorted(out)[len(out) // 2]


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--tiny", action="store_true")
  ap.add_argument("--widths", action="store_true")
  args = ap.parse_args(argv)
  import numpy as np
  import jax
  import jax.numpy as jnp
  from jax import lax
  from tensorflowonspark_tpu import ops
  from tensorflowonspark_tpu.models import transformer as tfm
  import importlib
  # module and function share a name: ``from ops import flash_attention`` is
  # the function
  fa = importlib.import_module("tensorflowonspark_tpu.ops.flash_attention")

  tiny = args.tiny
  slots, mx, seg, topk = (4, 256, 64, 32) if tiny else (16, 32768, 4096, 2048)
  h, hk, d, hi, di = 32, 4, 128, 16, 64
  rb = 128 if tiny else tfm._ROW_BLOCK
  cursor = mx // 2
  interp = ops.pallas_interpret()
  key = jax.random.PRNGKey(0)
  ks = jax.random.split(key, 12)
  readings = []

  def note(name, seconds, **kw):
    row = dict(reading=name, ms=seconds * 1e3, **kw)
    readings.append(row)
    print(json.dumps(row), flush=True)

  # cursors of the cell's mix: prompts 2048-30720 plus some output
  lens = jnp.asarray(np.linspace(mx // 16, mx - mx // 16, slots).astype(
      np.int32))
  col = jnp.arange(mx)

  if args.widths:
    iq4 = jax.random.normal(ks[2], (1, seg, hi, di), jnp.bfloat16)
    iw4 = jax.random.normal(ks[3], (1, seg, hi), jnp.float32)
    leaf = jax.random.normal(ks[1], (1, mx, tfm.INDEX_LANES), jnp.bfloat16)
    leaf = leaf.at[..., di:].set(0)
    for width in (mx // 8, mx // 4, mx // 2, mx):
      valid = col[None, :width] <= width - seg + jnp.arange(seg)[:, None]
      scores = jax.random.normal(ks[0], (seg, width), jnp.float32)
      note("select_threshold_%dx%d" % (seg, width), _time(
          jax.jit(lambda s, v: tfm.select_topk(s, v, topk)), scores, valid))
      note("index_scores_%dx%d_live_%d" % (seg, width, width), _time(
          jax.jit(lambda q, w, k: tfm.index_scores(q, w, k, live=width)),
          iq4, iw4, leaf[:, :width]))
    return _write(readings, tiny, "index_select_widths.json")

  # 1. the two selections at both shapes
  for rows, valid in ((slots, col[None] <= lens[:, None]),
                      (seg, col[None] <= cursor + jnp.arange(seg)[:, None])):
    scores = jax.random.normal(ks[0], (rows, mx), jnp.float32)
    note("select_threshold_%dx%d" % (rows, mx), _time(
        jax.jit(lambda s, v: tfm.select_topk(s, v, topk)), scores, valid))
    note("select_lax_top_k_%dx%d" % (rows, mx), _time(
        jax.jit(lambda s, v: lax.top_k(jnp.where(v, s, -jnp.inf), topk)),
        scores, valid))

  # 2. the index scores of both shapes
  leaf = jax.random.normal(ks[1], (slots, mx, tfm.INDEX_LANES), jnp.bfloat16)
  leaf = leaf.at[..., di:].set(0)
  iq = jax.random.normal(ks[2], (slots, 1, hi, di), jnp.bfloat16)
  iw = jax.random.normal(ks[3], (slots, 1, hi), jnp.float32)
  note("index_scores_%dx%d" % (slots, mx),
       _time(jax.jit(tfm.index_scores), iq, iw, leaf))
  iq4 = jax.random.normal(ks[2], (1, seg, hi, di), jnp.bfloat16)
  iw4 = jax.random.normal(ks[3], (1, seg, hi), jnp.float32)
  note("index_scores_%dx%d_live_%d" % (seg, mx, cursor + seg), _time(
      jax.jit(lambda q, w, k: tfm.index_scores(q, w, k, live=cursor + seg)),
      iq4, iw4, leaf[:1]))

  # 3. the decode read three ways
  ck = jax.random.normal(ks[4], (slots, mx, hk * d), jnp.bfloat16)
  cv = jax.random.normal(ks[5], (slots, mx, hk * d), jnp.bfloat16)
  q = jax.random.normal(ks[6], (slots, 1, h, d), jnp.bfloat16)
  k1 = jax.random.normal(ks[7], (slots, 1, hk, d), jnp.bfloat16)
  v1 = jax.random.normal(ks[8], (slots, 1, hk, d), jnp.bfloat16)
  keep = tfm.select_topk(jax.random.normal(ks[9], (slots, mx)),
                         col[None] <= lens[:, None], topk)
  own = jnp.take_along_axis(keep, lens[:, None], axis=1)
  pair = (keep[:, None, :], own[:, :, None])

  def dense(q, k1, v1, ck, cv, pair):
    return tfm._cached_attention(q, k1, v1, ck, cv, lens[:, None],
                                 lengths=lens, keep=pair)

  was = ops.pallas_kernels_enabled
  ops.pallas_kernels_enabled = lambda: False
  try:
    note("decode_read_dense_masked", _time(jax.jit(dense), q, k1, v1, ck, cv,
                                           pair))
  finally:
    ops.pallas_kernels_enabled = was
  if tiny or ops.pallas_kernels_enabled():
    note("decode_read_kernel_keep", _time(jax.jit(
        lambda q, k1, v1, ck, cv, pair: ops.decode_attention(
            q[:, 0], k1[:, 0], v1[:, 0], ck, cv, lens,
            keep=(pair[0][:, 0], pair[1][:, 0, 0]), interpret=interp)),
                                          q, k1, v1, ck, cv, pair))
    note("decode_read_kernel_no_keep", _time(jax.jit(
        lambda q, k1, v1, ck, cv: ops.decode_attention(
            q[:, 0], k1[:, 0], v1[:, 0], ck, cv, lens, interpret=interp)),
                                             q, k1, v1, ck, cv))

  def gathered(q, k1, v1, ck, cv, scores):
    _, idx = lax.top_k(jnp.where(col[None] < lens[:, None], scores, -jnp.inf),
                       topk)
    gk = jnp.take_along_axis(ck, idx[:, :, None], axis=1)   # [b, topk, c]
    gv = jnp.take_along_axis(cv, idx[:, :, None], axis=1)
    return tfm._cached_attention(q, k1, v1, gk, gv,
                                 jnp.full((slots, 1), topk), lengths=None)

  ops.pallas_kernels_enabled = lambda: False
  try:
    note("decode_read_gather_then_dense", _time(
        jax.jit(gathered), q, k1, v1, ck, cv,
        jax.random.normal(ks[9], (slots, mx))))
  finally:
    ops.pallas_kernels_enabled = was

  # 4. a chunk at a cursor under the mask: the operand, and XLA's einsums
  q4 = jax.random.normal(ks[6], (1, seg, h, d), jnp.bfloat16)
  rowk, rowv = ck[:1], cv[:1]
  keep4 = tfm.select_topk(jax.random.normal(ks[10], (1, seg, mx)),
                          col[None, None] <= cursor + jnp.arange(seg)[None, :,
                                                                     None],
                          topk)
  blocks = (cursor + seg - 1) // rb + 1

  def operand(q4, rowk, rowv, keep4):
    def one(j, partial):
      base = j * rb
      kj, vj = (lax.dynamic_slice_in_dim(c, base, rb, axis=1).reshape(
          1, rb, hk, d) for c in (rowk, rowv))
      return fa.merge_partials(*partial, *fa.flash_attention_block(
          q4, kj, vj, cursor, base, causal=True, interpret=interp,
          keep=lax.dynamic_slice_in_dim(keep4, base, rb, axis=2)))
    return lax.fori_loop(0, blocks, one, (
        jnp.zeros((1, seg, h, d), jnp.float32),
        jnp.full((1, h, seg), fa.NEG_INF, jnp.float32)))[0]

  def xla(q4, rowk, rowv, keep4):
    qg = q4.reshape(1, seg, hk, h // hk, d)

    def one(j, carry):
      m, l, acc = carry
      base = j * rb
      kj, vj = (lax.dynamic_slice_in_dim(c, base, rb, axis=1).reshape(
          1, rb, hk, d) for c in (rowk, rowv))
      s = jnp.einsum("bqkgd,btkd->bkgqt", qg, kj,
                     preferred_element_type=jnp.float32) / d ** 0.5
      kp = lax.dynamic_slice_in_dim(keep4, base, rb, axis=2)
      s = jnp.where(kp[:, None, None], s, -1e30)
      m_new = jnp.maximum(m, s.max(-1))
      p = jnp.exp(s - m_new[..., None])
      a = jnp.exp(m - m_new)
      return (m_new, a * l + p.sum(-1), a[..., None] * acc + jnp.einsum(
          "bkgqt,btkd->bkgqd", p.astype(jnp.bfloat16), vj,
          preferred_element_type=jnp.float32))

    m, l, acc = lax.fori_loop(0, blocks, one, (
        jnp.full((1, hk, h // hk, seg), -1e30, jnp.float32),
        jnp.zeros((1, hk, h // hk, seg), jnp.float32),
        jnp.zeros((1, hk, h // hk, seg, d), jnp.float32)))
    return acc / l[..., None]

  note("chunk_%d_at_%d_flash_keep_operand" % (seg, cursor),
       _time(jax.jit(operand), q4, rowk, rowv, keep4, reps=3))
  note("chunk_%d_at_%d_xla_masked" % (seg, cursor),
       _time(jax.jit(xla), q4, rowk, rowv, keep4, reps=3))

  return _write(readings, tiny, "index_select_time.json")


def _write(readings, tiny, name) -> int:
  import jax
  out_dir = os.path.join(ROOT, "chiprun_out")
  os.makedirs(out_dir, exist_ok=True)
  with open(os.path.join(out_dir, name), "w") as f:
    json.dump(dict(device=str(jax.devices()[0]), tiny=tiny,
                   readings=readings), f, indent=1)
  return 0


if __name__ == "__main__":
  sys.exit(main())
