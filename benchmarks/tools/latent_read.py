#!/usr/bin/env python3
"""Builder's reading (ISSUE 40's step zero), never part of a check's run: the
decode step's read of ONE latent leaf, timed alone on the chip.

    chiprun -- python3 benchmarks/tools/latent_read.py [--slots 24,32]

128 absorbed query heads over a leaf ``[slots, 16384, 640]`` (bf16) with the
slots at cursors drawn as the cell ``deepseek-v3-serve-backlog`` has them (a
prompt of its mix plus a share of its output), by each candidate:

* ``dense``: the contraction ``models/mla.py`` keeps for what the kernel does
  not take: the float32 query's three bf16 terms against ALL 16384 rows, a
  mask, the probabilities' three terms against all rows again;
* ``kernel``: ``ops.decode_attention`` handed the leaf as K AND as V (what
  ``models/mla.py`` does: two DMAs and two buffers a block, the probabilities
  in three exact terms), stopping at each slot's cursor.

Each is ``--calls`` calls inside one jit, the output fed back into the query
so that the calls serialize; the second run of the program is timed.  Prints
one JSON line and writes ``chiprun_out/latent_read.json``.
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

HEADS, LANES, RANK, MAX_SEQ = 128, 640, 512, 16384
SCALE = 192 ** -0.5 * 1.87385


def cursors(slots: int, seed: int):
  import numpy as np
  from benchmarks.lib import loader
  mix = loader.load_json(os.path.join(
      ROOT, "benchmarks", "traffic", "serve-backlog-16k-latent.json"))["mix"]
  rng = np.random.default_rng(seed)
  p = rng.choice(mix["prompt_lens"], slots, p=np.asarray(
      mix["prompt_weights"]) / sum(mix["prompt_weights"]))
  o = rng.choice(mix["output_lens"], slots, p=np.asarray(
      mix["output_weights"]) / sum(mix["output_weights"]))
  return np.minimum(p + (o * rng.random(slots)).astype(np.int64),
                    MAX_SEQ - 1).astype(np.int32)


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--slots", default="24,32")
  ap.add_argument("--calls", type=int, default=50)
  ap.add_argument("--seed", type=int, default=40)
  args = ap.parse_args(argv)
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu import ops
  from tensorflowonspark_tpu.models import mla
  from tensorflowonspark_tpu.models import transformer as tfm
  out = dict(device=jax.devices()[0].device_kind, calls=args.calls, runs=[])
  for slots in (int(s) for s in args.slots.split(",")):
    cur = cursors(slots, args.seed)
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    leaf = jax.random.normal(keys[0], (slots, MAX_SEQ, LANES), jnp.bfloat16)
    own = jax.random.normal(keys[1], (slots, 1, LANES), jnp.bfloat16)
    q0 = jax.random.normal(keys[2], (slots, HEADS, LANES), jnp.bfloat16)
    idx = jnp.asarray(cur)

    def dense(q, leaf, own, idx):
      q_abs = q.astype(jnp.float32)[:, None]               # [b, 1, h, c]
      s_cache = tfm._cache_contract(
          "bnc,bkc->bnk", q_abs.reshape(slots, HEADS, LANES),
          leaf).reshape(slots, 1, HEADS, -1) * SCALE
      own_f = own.astype(jnp.float32)
      s_own = jnp.einsum("bqhc,bkc->bqhk", q_abs, own_f) * SCALE
      e_cache, e_own, total = mla._two_part_softmax(
          s_cache, s_own, idx[:, None], 1)
      o = tfm._cache_contract(
          "bnk,bkc->bnc", e_cache.reshape(slots, HEADS, -1), leaf)
      o = o + jnp.einsum("bqhk,bkc->bqhc", e_own, own_f)[:, 0]
      return o / total[:, 0, :, None]

    def kernel(q, leaf, own, idx):
      return ops.decode_attention(q, own, own, leaf, leaf, idx, scale=SCALE)

    cands = dict(dense=dense, kernel=kernel)
    row = dict(slots=slots, cursor_mean=float(cur.mean()),
               cursor_max=int(cur.max()), live_rows=int(cur.sum()),
               live_bytes=int(cur.sum()) * LANES * 2, ms_a_call={})
    ref = None
    for name, fn in cands.items():
      if name == "kernel" and not ops.decode_attention_supports(
          q0.shape, q0.dtype, leaf.shape, leaf.dtype):
        row["ms_a_call"][name] = None      # the blocks do not fit the budget
        continue
      # the leaf goes in as an ARGUMENT: closed over, its 0.5 GB would be a
      # constant of every program and each would take minutes to compile
      many = jax.jit(lambda q, *rest, fn=fn: jax.lax.fori_loop(
          0, args.calls, lambda _, qq: (qq.astype(jnp.float32) + 1e-3 * fn(
              qq, *rest)).astype(jnp.bfloat16), q))
      rest = (leaf, own, idx)
      first = jax.block_until_ready(jax.jit(fn)(q0, *rest))
      if ref is None:
        ref = first
      row.setdefault("max_abs_diff_to_dense", {})[name] = float(
          jnp.abs(first[..., :RANK] - ref[..., :RANK]).max())
      jax.block_until_ready(many(q0, *rest))
      t0 = time.perf_counter()
      jax.block_until_ready(many(q0, *rest))
      row["ms_a_call"][name] = (time.perf_counter() - t0) / args.calls * 1e3
    out["runs"].append(row)
  os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
  with open(os.path.join(ROOT, "chiprun_out", "latent_read.json"), "w") as f:
    json.dump(out, f, indent=1)
  print(json.dumps(out))
  return 0


if __name__ == "__main__":
  sys.exit(main())
