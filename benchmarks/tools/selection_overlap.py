#!/usr/bin/env python3
"""Builder's reading, never part of a check's run: how many of the rows the
float32 reference's indexer chooses does the PROGRAM's (bf16) choose?

    chiprun -- python3 benchmarks/tools/selection_overlap.py --seed N [--tokens 8192]

bf16 against float32 moves near-ties at the ``topk``-th place of a query's
index scores, and a moved row is another key and value under the softmax: the
served-logit gap cannot say how many moved, this does.  A seeded sequence of
``--tokens`` token ids goes through the reference (float32; each layer's input
kept, ``families/keye_vl2.chosen_rows`` over it) and through the program's
full forward at the configuration's compute dtype (its ``select_topk`` masks
captured, layer by layer, over ITS OWN stream); per layer, over the queries
with more than ``topk`` candidates: the share of the reference's chosen rows
that the program chose too.  Prints one JSON line a layer and writes
``chiprun_out/selection_overlap-<seed>.json``.  ``--rehearse`` runs the
configuration's rehearsal sizes in float32 on the CPU, where the share is 100.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--seed", type=int, default=0)
  ap.add_argument("--tokens", type=int, default=8192)
  ap.add_argument("--rehearse", action="store_true")
  args = ap.parse_args(argv)
  import numpy as np
  import jax
  import jax.numpy as jnp
  from benchmarks.lib import loader
  from benchmarks.lib import needs_keye_vl2 as needs
  from tensorflowonspark_tpu.models import transformer as tfm
  config = loader.load_json(needs.CONFIG)
  if args.rehearse:
    config = dict({k: v for k, v in config.items() if k != "rehearse"},
                  **config["rehearse"])
    args.tokens = min(args.tokens, 64)
  fam = loader.load_module("families", config["family"])
  z = fam.sizes(config)
  n, topk = args.tokens, z["topk"]
  store = config.get("compute_dtype", "bfloat16")
  toks = np.random.default_rng([args.seed, 21]).integers(
      0, z["vocab"], (1, n), dtype=np.int32)
  weights = fam.make_weights(args.seed, config, store)
  streams = []
  jax.block_until_ready(
      fam.reference_logits(weights, jnp.asarray(toks), config,
                           streams=streams)[0, -1])
  del weights
  params = fam.program_params(args.seed, config, store)
  chosen, real = [], tfm.select_topk
  tfm.select_topk = lambda *a: chosen.append(real(*a)) or chosen[-1]
  try:
    tfm.Transformer(fam.program_config(config, n)).apply(
        {"params": params}, jnp.asarray(toks))
  finally:
    tfm.select_topk = real
  weights = fam.make_weights(args.seed, config, store)
  limited = np.arange(n) >= topk
  rows = []
  for i, (x, got) in enumerate(zip(streams, chosen)):
    want = np.asarray(fam.chosen_rows(weights, x, config, i))[0][limited]
    got = np.asarray(got)[0][limited]
    row = dict(layer=i, queries=int(limited.sum()), topk=topk,
               share=100.0 * float((want & got).sum()) / float(want.sum())
               if limited.any() else None)
    rows.append(row)
    print(json.dumps(row), flush=True)
  out_dir = os.path.join(ROOT, "chiprun_out")
  os.makedirs(out_dir, exist_ok=True)
  with open(os.path.join(out_dir, "selection_overlap-%d.json" % args.seed),
            "w") as f:
    json.dump(dict(seed=args.seed, tokens=n, dtype=store,
                   device=str(jax.devices()[0]), layers=rows), f, indent=1)
  return 0


if __name__ == "__main__":
  sys.exit(main())
