#!/usr/bin/env python3
"""Builder's reading, never part of a check's run: how often the program
chooses another HELD expert than the reference does.

    chiprun -- python3 benchmarks/tools/router_ties.py [--seed N] [--tokens 512]

The router runs in float32 on both sides, but its INPUT is the residual
stream, which the program computes in bf16: where the 8th and the 9th score
are nearly equal the two sides can fall differently, and a token then passes
another expert. That is rounding, not a fault, and it is why a served token's
logit gap is wider here than for a dense model; this prints how often it
happens at the published widths (one forward of ``--tokens`` seeded tokens
through the program, then through the reference, one after the other: both
hold 8.6 GB of weights). Writes ``chiprun_out/router_ties.json``.
"""

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--seed", type=int, default=2147483677)
  ap.add_argument("--tokens", type=int, default=512)
  ap.add_argument("--rehearse", action="store_true")
  args = ap.parse_args(argv)
  import numpy as np
  import jax
  import jax.numpy as jnp
  from benchmarks.lib import loader
  from benchmarks.lib import needs_kimi_linear as needs
  from tensorflowonspark_tpu.models import transformer as tfm
  fam = loader.load_module("families", "kimi_linear")
  config = loader.load_json(needs.CONFIG)
  rehearse = config.pop("rehearse")
  if args.rehearse:
    config.update(rehearse, compute_dtype="bfloat16")
  z = fam.sizes(config)
  toks = np.random.default_rng([args.seed, 13]).integers(
      0, z["vocab"], (1, args.tokens), dtype=np.int32)

  model = tfm.Transformer(fam.program_config(config, args.tokens))
  params = fam.program_params(args.seed, config, "bfloat16")
  _, sown = jax.jit(lambda p, t: model.apply(
      {"params": p}, t, mutable=["counters"]))(params, toks)
  # [expert layers, T, held]: which held experts each token chose
  hit = np.stack([np.asarray(sown["counters"]["layer_%d" % i]["moe"]["hit"][0])
                  for i in range(z["dense_layers"], z["layers"])])
  del params, sown
  gc.collect()

  weights = fam.make_weights(args.seed, config, "bfloat16")

  def routing(w, t):
    out = []
    fam.reference_logits(w, t, config, routing=out)
    return jnp.stack(out)

  local = np.asarray(jax.jit(routing)(weights, toks))[:, 0] - z["first"]
  ref = (local[..., None] == np.arange(z["held"])).any(axis=2)   # [L, T, held]
  differs = (hit != ref).any(axis=2)                  # a (layer, token) pair
  result = dict(
      device=jax.devices()[0].device_kind, seed=args.seed,
      tokens=args.tokens, expert_layers=int(hit.shape[0]),
      held_assignments_program=int(hit.sum()),
      held_assignments_reference=int(ref.sum()),
      layer_tokens=int(differs.size),
      layer_tokens_with_another_held_expert=int(differs.sum()),
      share=float(differs.mean()),
      tokens_with_any=int(differs.any(axis=0).sum()),
      by_layer=[int(x) for x in differs.sum(axis=1)])
  out_dir = os.path.join(ROOT, "chiprun_out")
  os.makedirs(out_dir, exist_ok=True)
  with open(os.path.join(out_dir, "router_ties.json"), "w") as f:
    json.dump(result, f, indent=1)
  print(json.dumps(result))
  return 0


if __name__ == "__main__":
  sys.exit(main())
