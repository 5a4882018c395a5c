#!/usr/bin/env python3
"""Builder's reading (ISSUE 41's step zero), never part of a check's run: the
grouped expert product, timed alone on the chip at the four expert cells' real
shapes.

    chiprun -- python3 benchmarks/tools/expert_product_time.py [--only trinity]

For each configuration its gate product (``[rows, d] x [held, d, f]``) and its
down product (``[rows, f] x [held, f, d]``), at a DECODE step's rows and at the
largest prefill CHUNK's, the rows sorted by group with the unheld ones last as
``held_experts_ffn`` hands them in: ``held`` of the rows have a group (the
mix's share), spread at random over ``touched`` of the groups (what the ledger
reads in a step; a chunk touches every group). By each candidate:

* ``ragged_dot``: ``lax.ragged_dot`` over ALL the rows, as the parent calls it;
* ``ragged_dot_held``: the same over the first rows only, as many as have a
  group (rounded up to 128): does its time follow the rows handed in or the
  rows held?
* ``kernel``: ``ops.expert_product``; with ``--sweep`` also at other matrix
  block sizes and row tiles (``BLOCK_BYTES``, ``ROWS``).

Each is ``calls`` calls inside one jit, a scalar of the output fed back into
the group sizes so that the calls serialize; the second run of the program is
timed. ``bytes_ms`` is the touched matrices' bytes over the HBM peak. Prints
one JSON line a case and writes ``chiprun_out/expert_product_time.json``.
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

#: (held experts, d, f, decode rows, chunk rows, terms a row, the mix's held
#: share of the rows, the share of the experts a decode step touches)
SHAPES = {
    "trinity": (32, 3072, 3072, 24 * 4, 2048 * 4, 1, 0.12, 0.29),
    "mimo": (16, 4096, 2048, 48 * 8, 2048 * 8, 1, 0.06, 0.73),
    "deepseek": (16, 7168, 2048, 24 * 8, 2048 * 8, 1, 0.06, 0.50),
    "kimi": (16, 2304, 1024, 48 * 8, 512 * 8, 3, 0.055, 0.51),
}


def group_sizes(rows: int, groups: int, held: float, touched: float, seed: int):
  """``held`` of ``rows`` spread over ``touched`` of ``groups``: each touched
  group one row, the rest at random."""
  import numpy as np
  rng = np.random.default_rng(seed)
  n_held = max(1, int(round(held * rows)))
  n_touched = min(n_held, max(1, int(round(touched * groups))))
  which = np.sort(rng.choice(groups, n_touched, replace=False))
  sizes = np.zeros(groups, np.int64)
  sizes[which] = 1 + rng.multinomial(n_held - n_touched,
                                     np.ones(n_touched) / n_touched)
  return sizes.astype(np.int32)


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--only", default="")
  ap.add_argument("--sweep", action="store_true")
  ap.add_argument("--seed", type=int, default=41)
  ap.add_argument("--tiny", action="store_true",
                  help="one toy shape: the CPU rehearsal of this script")
  args = ap.parse_args(argv)
  import jax
  import jax.numpy as jnp
  from jax import lax
  from benchmarks.lib import peaks
  from tensorflowonspark_tpu import ops
  # the module, not the function of the same name that ``ops`` exports
  import importlib
  ep = importlib.import_module("tensorflowonspark_tpu.ops.expert_product")
  kind = jax.devices()[0].device_kind
  # the rehearsal runs on the CPU, which has no peak: it reads no bytes' time
  peak = None if args.tiny else peaks.chip_peaks(kind)["hbm_bytes_per_s"]
  interpret = ops.pallas_interpret()
  cases = []

  def timed(fn, lhs, rhs, sizes, calls):
    def many(lhs, rhs, sizes):
      def body(_, s):
        out = fn(lhs, rhs, s)
        return s + (out[0, 0] > 1e30).astype(s.dtype)
      return lax.fori_loop(0, calls, body, sizes)
    many = jax.jit(many)
    jax.block_until_ready(many(lhs, rhs, sizes))
    t0 = time.perf_counter()
    jax.block_until_ready(many(lhs, rhs, sizes))
    return (time.perf_counter() - t0) / calls * 1e3

  def ragged(lhs, rhs, sizes):
    return lax.ragged_dot(lhs, rhs, sizes, preferred_element_type=jnp.float32)

  shapes = {"toy": (4, 256, 128, 16, 512, 3, 0.2, 0.5)} if args.tiny \
      else SHAPES
  for name, (g, d, f, step, chunk, terms, held, touched) in shapes.items():
    if args.only and name not in args.only.split(","):
      continue
    for product, (k, n) in (("gate", (d, f)), ("down", (f, d))):
      rhs = jax.random.normal(jax.random.PRNGKey(args.seed), (g, k, n),
                              jnp.bfloat16) * 0.02
      for shape, rows, share in (("decode", step, touched),
                                 ("chunk", chunk, 1.0)):
        sizes = group_sizes(rows, g, held, share, args.seed) * terms
        m = rows * terms
        lhs = jax.random.normal(jax.random.PRNGKey(args.seed + 1), (m, k),
                                jnp.bfloat16)
        n_held = int(sizes.sum())
        calls = 50 if shape == "decode" else 20
        sz = jnp.asarray(sizes)
        case = dict(config=name, product=product, shape=shape, m=m, k=k, n=n,
                    groups=g, held_rows=n_held,
                    touched=int((sizes > 0).sum()),
                    bytes_ms=peak and float(
                        (sizes > 0).sum()) * k * n * 2 / peak * 1e3,
                    ms={})
        want = jax.jit(ragged)(lhs, rhs, sz)
        got = ep.expert_product(lhs, rhs, sz, interpret=interpret)
        case["max_abs_diff"] = float(jnp.abs(
            got[:n_held] - want[:n_held]).max())
        case["tail_abs_max"] = float(jnp.abs(got[n_held:]).max()) \
            if n_held < m else 0.0
        case["ms"]["ragged_dot"] = timed(ragged, lhs, rhs, sz, calls)
        front = min(m, -(-n_held // 128) * 128)
        case["ms"]["ragged_dot_held"] = timed(ragged, lhs[:front], rhs, sz,
                                              calls)
        case["tiles"] = ep._tiles(m, k, n)
        case["ms"]["kernel"] = timed(
            lambda a, b, s: ep.expert_product(a, b, s, interpret=interpret),
            lhs, rhs, sz, calls)
        if args.sweep:
          was = ep.BLOCK_BYTES, ep.ROWS
          for block, tile in ((2 << 20, 128), (4 << 20, 128), (16 << 20, 128),
                              (8 << 20, 64), (8 << 20, 256)):
            ep.BLOCK_BYTES, ep.ROWS = block, tile
            if ep._tiles(m, k, n) == case["tiles"] \
                or ep._vmem_bytes(m, k, n) > ep.VMEM_BUDGET:
              continue
            # a new closure a setting: the jit's cache is keyed by the
            # function, and the tiles are read while it traces
            case["ms"]["kernel_%dM_%d" % (block >> 20, tile)] = timed(
                lambda a, b, s: ep._call(a, b, s, interpret), lhs, rhs, sz,
                calls)
          ep.BLOCK_BYTES, ep.ROWS = was
        print(json.dumps(case), flush=True)
        cases.append(case)
  os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
  with open(os.path.join(ROOT, "chiprun_out", "expert_product_time.json"),
            "w") as fh:
    json.dump(dict(device=kind, cases=cases), fh, indent=1)
  return 0


if __name__ == "__main__":
  sys.exit(main())
