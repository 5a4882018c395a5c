"""U-Net segmentation, multi-worker.

Parity with the reference's ``examples/segmentation/segmentation_spark.py``
(MobileNetV2-U-Net multi-worker training): each node trains the flax U-Net
on its synthetic shard and the chief exports the bundle.

Run:  python examples/segmentation/segmentation.py --executors 2 --steps 20
"""

import argparse
import os
import sys

# allow running straight from a repo checkout (no install needed)
sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir)))


def main_fn(args, ctx):
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import segmentation as seg

  images, masks = seg.synthetic_dataset(args.num_samples, size=args.size,
                                        seed=ctx.executor_id)
  state = seg.create_state(jax.random.PRNGKey(0),
                           model=seg.UNet(encoder_filters=(16, 32, 64)),
                           image_shape=(args.size, args.size, 3))
  bs = args.batch_size
  for step in range(args.steps):
    lo = (step * bs) % max(1, args.num_samples - bs + 1)
    state, loss = seg.train_step(state, jnp.asarray(images[lo:lo + bs]),
                                 jnp.asarray(masks[lo:lo + bs]))
    if step % 5 == 0:
      print("node %d step %d loss %.4f"
            % (ctx.executor_id, step, float(loss)))
  if ctx.is_chief and args.export_dir:
    ctx.export_model(jax.device_get(state.params), args.export_dir)


if __name__ == "__main__":
  parser = argparse.ArgumentParser()
  parser.add_argument("--executors", type=int, default=2)
  parser.add_argument("--steps", type=int, default=20)
  parser.add_argument("--batch_size", type=int, default=8)
  parser.add_argument("--num_samples", type=int, default=64)
  parser.add_argument("--size", type=int, default=64)
  parser.add_argument("--export_dir", default=None)
  args = parser.parse_args()

  from tensorflowonspark_tpu import cluster
  from tensorflowonspark_tpu.cluster import InputMode
  from tensorflowonspark_tpu.engine import LocalEngine

  engine = LocalEngine(num_executors=args.executors)
  try:
    c = cluster.run(engine, main_fn, tf_args=args,
                    input_mode=InputMode.FILES)
    c.shutdown()
    print("segmentation training complete")
  finally:
    engine.stop()
