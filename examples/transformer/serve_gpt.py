"""Serve a Transformer LM through the pipeline bundle path.

Exports a (toy) causal LM as a model bundle, then runs batched KV-cache
decoding over prompt partitions with ``TFModel.transform`` on real
executor processes — the serving analog of the reference's
batch-inference flow (Spark ML TFModel / Inference.scala), with
``collect=False`` streaming so the driver never holds the full output.

  python examples/transformer/serve_gpt.py --steps 8 --prompts 32
"""

import argparse
import os
import sys

# allow running straight from a repo checkout (no install needed)
sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir)))

if __name__ == "__main__":
  parser = argparse.ArgumentParser()
  parser.add_argument("--steps", type=int, default=8,
                      help="tokens to generate per prompt")
  parser.add_argument("--prompts", type=int, default=32)
  parser.add_argument("--prompt_len", type=int, default=8)
  parser.add_argument("--temperature", type=float, default=0.0)
  parser.add_argument("--export_dir", default="/tmp/tos_tpu_serve_gpt")
  parser.add_argument("--executors", type=int, default=2)
  parser.add_argument("--tensor", type=int, default=1,
                      help="tensor-parallel degree per executor: the "
                           "bundle carries a MeshSpec and each executor "
                           "builds its mesh from its own devices (heads "
                           "+ KV cache sharded, batch over data)")
  args = parser.parse_args()

  import numpy as np
  import jax
  from tensorflowonspark_tpu import pipeline
  from tensorflowonspark_tpu.engine import LocalEngine
  from tensorflowonspark_tpu.models import transformer as tfm

  cfg = tfm.TransformerConfig(vocab_size=256, num_layers=2, num_heads=4,
                              num_kv_heads=2, d_model=128, d_ff=256,
                              max_seq_len=64, remat=False)
  state = tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=16)
  mesh_spec = None
  if args.tensor > 1:
    from tensorflowonspark_tpu.parallel import mesh as mesh_lib
    mesh_spec = mesh_lib.MeshSpec(data=-1, tensor=args.tensor)
  pipeline.export_bundle(
      state.params,
      tfm.make_serving_predict_fn(cfg, args.steps,
                                  temperature=args.temperature,
                                  mesh_spec=mesh_spec),
      args.export_dir)
  print("exported bundle to", args.export_dir)

  rng = np.random.RandomState(0)
  prompts = [(rng.randint(0, 256, args.prompt_len).tolist(),)
             for _ in range(args.prompts)]
  partitions = [prompts[i::args.executors] for i in range(args.executors)]

  engine = LocalEngine(num_executors=args.executors)
  try:
    model = pipeline.TFModel({"export_dir": args.export_dir,
                              "batch_size": 8})
    served = 0
    for tokens in model.transform(engine, partitions, collect=False):
      if served < 3:
        print("prompt+generation:", tokens)
      served += 1
  finally:
    engine.stop()
  print("served %d prompts x %d generated tokens each"
        % (served, args.steps))
  assert served == args.prompts
