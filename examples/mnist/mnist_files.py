"""MNIST, FILES input mode: every node reads/generates its own data shard.

Parity with the reference's ``examples/mnist/keras/mnist_tf.py`` (each
worker reads tfds itself under MultiWorkerMirroredStrategy) — here each
node trains the flax MLP on its shard; multi-node gradient sync comes from
``jax.distributed`` + data-parallel sharding when the cluster has >1 node.

Run:  python examples/mnist/mnist_files.py --executors 2 --steps 200
"""

import argparse
import os
import sys

# allow running straight from a repo checkout (no install needed)
sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir)))


def main_fn(args, ctx):
  import jax
  from tensorflowonspark_tpu.models import mnist

  images, labels = mnist.synthetic_dataset(
      args.num_samples, seed=ctx.executor_id)
  state = mnist.create_state(jax.random.PRNGKey(args.seed),
                             model=mnist.CNN() if args.model == "cnn"
                             else mnist.MLP())
  bs = args.batch_size
  for step in range(args.steps):
    lo = (step * bs) % max(1, len(images) - bs + 1)
    state, loss = mnist.train_step(state, images[lo:lo + bs],
                                   labels[lo:lo + bs])
    if step % 50 == 0:
      print("node %d step %d loss %.4f" % (ctx.executor_id, step,
                                           float(loss)))
  _, acc = mnist.eval_step(state, images, labels)
  print("node %d final accuracy %.3f" % (ctx.executor_id, float(acc)))
  if ctx.is_chief and args.export_dir:
    ctx.export_model(state.params, args.export_dir)


if __name__ == "__main__":
  parser = argparse.ArgumentParser()
  parser.add_argument("--executors", type=int, default=2)
  parser.add_argument("--steps", type=int, default=200)
  parser.add_argument("--batch_size", type=int, default=64)
  parser.add_argument("--num_samples", type=int, default=2048)
  parser.add_argument("--model", choices=["mlp", "cnn"], default="mlp")
  parser.add_argument("--seed", type=int, default=0)
  parser.add_argument("--export_dir", default=None)
  parser.add_argument("--tensorboard", action="store_true")
  args = parser.parse_args()

  from tensorflowonspark_tpu import cluster
  from tensorflowonspark_tpu.cluster import InputMode
  from tensorflowonspark_tpu.engine import LocalEngine

  engine = LocalEngine(num_executors=args.executors)
  try:
    c = cluster.run(engine, main_fn, tf_args=args,
                    input_mode=InputMode.FILES,
                    tensorboard=args.tensorboard)
    c.shutdown()
    print("training complete; tensorboard:", c.tensorboard_url())
  finally:
    engine.stop()
