#!/usr/bin/env python3
"""Builder's reading, never part of a check's run: step zero of PR 45, the
exact selection's threshold search as XLA runs it against ``ops.select_topk``'s
kernel, ALONE on the chip at the ``keye-vl2-serve-backlog`` cell's shapes.

    chiprun -- python3 benchmarks/tools/select_kernel_time.py [--tiny]

2048 of up to 32768, at a decode step's ``[16, 32768]`` (each slot's last
candidate a cursor of the cell's mix, 2048-30720) and at a prefill chunk's
``[4096, 32768]`` (the chunk ending at 4096, 8192, 16384 and 32768: a query's
last candidate is its own position; and once with every column every row's
candidate), two ways over the same seeded scores:

- ``xla``: ``models.transformer.select_topk`` handed the candidates as a
  boolean mask, which keeps the search as XLA operations (32 passes over the
  row in HBM, whatever the cursor: the parent's program);
- ``kernel``: ``ops.select_topk`` handed each row's last candidate.

A reading is the device's busy seconds a call in a profiler trace of ``reps``
calls (``benchmarks.lib.trace``: the union of the device's operations), not
the host's clock: a step's search is shorter than a dispatch. Beside each
pair, ``differ``: the number of entries in which the two masks differ, on
normal scores and on scores quantised to 16 levels (every row ties at its
threshold: the earlier position stays, and the kernel's second search runs);
it has to read 0 everywhere.

Prints one JSON line a reading and writes them to
``chiprun_out/select_kernel_time.json``.  ``--tiny`` is the CPU rehearsal
(small shapes, the kernel in interpret mode, the host's clock in place of a
trace): it proves the tool runs, its numbers mean nothing.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)


def _device_seconds(fn, args, reps: int, tiny: bool):
  """Busy device seconds a call of ``fn(*args)`` (jitted; compiled by a
  first call) over ``reps`` traced calls; the host's clock under ``--tiny``."""
  import jax
  from benchmarks.lib import trace
  jax.block_until_ready(fn(*args))
  if tiny:
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return time.perf_counter() - t0
  directory = tempfile.mkdtemp(prefix="select_kernel_time_")
  try:
    with jax.profiler.trace(directory):
      for _ in range(reps):
        jax.block_until_ready(fn(*args))
    summary = trace.reduce_directory(directory)
  finally:
    shutil.rmtree(directory, ignore_errors=True)
  if not summary:
    raise SystemExit("the trace holds no device operation")
  return summary["busy_s"] / reps


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--tiny", action="store_true")
  ap.add_argument("--reps", type=int, default=5)
  ap.add_argument("--seed", type=int, default=0)
  args = ap.parse_args(argv)
  import numpy as np
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu import ops
  from tensorflowonspark_tpu.models import transformer as tfm

  tiny = args.tiny
  slots, mx, seg, topk = (4, 512, 64, 32) if tiny else (16, 32768, 4096, 2048)
  interp = ops.pallas_interpret()
  col = jnp.arange(mx)
  readings = []

  xla = jax.jit(lambda s, last: tfm.select_topk(
      s, col[None, :] <= last[:, None], topk))
  kernel = jax.jit(lambda s, last: ops.select_topk(s, last, topk,
                                                   interpret=interp))
  differ = jax.jit(lambda a, b: jnp.sum(a != b))

  # a step: cursors of the cell's mix (prompts 2048-30720 plus some output)
  cases = [("step", slots, np.linspace(mx // 16, mx - mx // 16, slots))]
  # a chunk ending at ``end``: a query's last candidate is its own position
  cases += [("chunk_ending_%d" % end, seg, end - seg + np.arange(seg))
            for end in (mx // 8, mx // 4, mx // 2, mx)]
  cases += [("chunk_all_live", seg, np.full(seg, mx - 1))]

  for name, rows, last in cases:
    last = jnp.asarray(last.astype(np.int32))
    normal = jax.random.normal(jax.random.PRNGKey(args.seed), (rows, mx),
                               jnp.float32)
    row = dict(reading=name, rows=rows, n=mx, k=topk,
               live_columns=int(last.max()) + 1)
    for kind, scores in (("normal", normal), (
        "quantised", jnp.clip(jnp.round(normal * 2), -8, 7) / 2)):
      row["differ_" + kind] = int(differ(xla(scores, last),
                                         kernel(scores, last)))
      row["kernel_ms_" + kind] = 1e3 * _device_seconds(
          kernel, (scores, last), args.reps, tiny)
    row["xla_ms"] = 1e3 * _device_seconds(xla, (normal, last), args.reps, tiny)
    row["xla_over_kernel"] = row["xla_ms"] / row["kernel_ms_normal"]
    readings.append(row)
    print(json.dumps(row), flush=True)

  out_dir = os.path.join(ROOT, "chiprun_out")
  os.makedirs(out_dir, exist_ok=True)
  with open(os.path.join(out_dir, "select_kernel_time.json"), "w") as f:
    json.dump(dict(device=str(jax.devices()[0]), tiny=tiny,
                   readings=readings), f, indent=1)
  return 1 if any(r["differ_normal"] or r["differ_quantised"]
                  for r in readings) else 0


if __name__ == "__main__":
  sys.exit(main())
