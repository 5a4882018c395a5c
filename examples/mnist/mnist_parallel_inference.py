"""MNIST embarrassingly-parallel inference with the barrier runner.

Parity with the reference's ``examples/mnist/keras/mnist_inference.py``
(TFParallel.run): independent single-node instances, gang-scheduled, each
processing its own file shard — no cluster, no feed plane.

Run:  python examples/mnist/mnist_parallel_inference.py --executors 2
"""

import argparse
import os
import sys

# allow running straight from a repo checkout (no install needed)
sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir)))


def infer_fn(args, ctx):
  import jax
  from tensorflowonspark_tpu.models import mnist

  # each task scores its own shard (sharded by task id among gang size)
  n = max(1, len(ctx.cluster_spec.get("worker", [1])))
  images, labels = mnist.synthetic_dataset(args.num_samples,
                                           seed=args.seed)
  images, labels = images[ctx.task_index::n], labels[ctx.task_index::n]
  state = mnist.create_state(jax.random.PRNGKey(0))
  for _ in range(args.warm_steps):  # quick fit so predictions are sane
    state, _ = mnist.train_step(state, images[:64], labels[:64])
  _, acc = mnist.eval_step(state, images, labels)
  return {"task": ctx.task_index, "rows": int(len(images)),
          "accuracy": float(acc)}


if __name__ == "__main__":
  parser = argparse.ArgumentParser()
  parser.add_argument("--executors", type=int, default=2)
  parser.add_argument("--num_samples", type=int, default=1024)
  parser.add_argument("--warm_steps", type=int, default=60)
  parser.add_argument("--seed", type=int, default=0)
  parser.add_argument("--no_barrier", action="store_true")
  args = parser.parse_args()

  from tensorflowonspark_tpu.engine import LocalEngine
  from tensorflowonspark_tpu.parallel import runner

  engine = LocalEngine(num_executors=args.executors)
  try:
    results = runner.run(engine, infer_fn, tf_args=args,
                         num_tasks=args.executors,
                         use_barrier=not args.no_barrier)
    for r in sorted(results, key=lambda r: r["task"]):
      print("task %d: %d rows, accuracy %.3f"
            % (r["task"], r["rows"], r["accuracy"]))
  finally:
    engine.stop()
