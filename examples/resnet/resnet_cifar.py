"""ResNet-56 on CIFAR-sized data, distributed over cluster nodes.

Parity with the reference's ``examples/resnet/resnet_cifar_dist.py``
(ResNet-56 CIFAR under a tf.distribute strategy chosen by flag): each node
trains the flax ResNet on its shard; with real TPU chips, pass
``--chips_per_node`` so co-located nodes split the host's chips.

Run:  python examples/resnet/resnet_cifar.py --executors 2 --steps 30
"""

import argparse
import os
import sys

# allow running straight from a repo checkout (no install needed)
sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir)))


def main_fn(args, ctx):
  import numpy as np
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import resnet

  rng = np.random.RandomState(ctx.executor_id)
  images = rng.rand(args.num_samples, 32, 32, 3).astype("float32")
  labels = rng.randint(0, 10, args.num_samples).astype("int32")

  model = resnet.ResNet56CIFAR()
  state = resnet.create_state(jax.random.PRNGKey(0), model,
                              image_shape=(32, 32, 3),
                              learning_rate=args.lr)
  bs = args.batch_size
  for step in range(args.steps):
    lo = (step * bs) % max(1, args.num_samples - bs + 1)
    state, loss = resnet.train_step(state, jnp.asarray(images[lo:lo + bs]),
                                    jnp.asarray(labels[lo:lo + bs]))
    if step % 10 == 0:
      print("node %d step %d loss %.4f"
            % (ctx.executor_id, step, float(loss)))
  if ctx.is_chief and args.export_dir:
    ctx.export_model(jax.device_get(state.params), args.export_dir)


if __name__ == "__main__":
  parser = argparse.ArgumentParser()
  parser.add_argument("--executors", type=int, default=2)
  parser.add_argument("--steps", type=int, default=30)
  parser.add_argument("--batch_size", type=int, default=128)
  parser.add_argument("--num_samples", type=int, default=1024)
  parser.add_argument("--lr", type=float, default=0.05)
  parser.add_argument("--chips_per_node", type=int, default=0)
  parser.add_argument("--export_dir", default=None)
  args = parser.parse_args()

  from tensorflowonspark_tpu import cluster
  from tensorflowonspark_tpu.cluster import InputMode
  from tensorflowonspark_tpu.engine import LocalEngine

  engine = LocalEngine(num_executors=args.executors)
  try:
    c = cluster.run(engine, main_fn, tf_args=args,
                    input_mode=InputMode.FILES,
                    chips_per_node=args.chips_per_node)
    c.shutdown()
    print("resnet training complete")
  finally:
    engine.stop()
