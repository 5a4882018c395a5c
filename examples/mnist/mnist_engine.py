"""MNIST, ENGINE input mode: the engine pushes partitioned rows into each
node's DataFeed.

Parity with the reference's ``examples/mnist/keras/mnist_spark.py``
(InputMode.SPARK + DataFeed generator): rows stream through the feed hub
in chunks, the node assembles device batches, and the driver replays the
dataset for N epochs.

Run:  python examples/mnist/mnist_engine.py --executors 2 --epochs 2
"""

import argparse
import os
import sys

# allow running straight from a repo checkout (no install needed)
sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir)))


def main_fn(args, ctx):
  import jax
  import numpy as np
  from tensorflowonspark_tpu.data.readers import device_prefetch, \
      slab_batches
  from tensorflowonspark_tpu.models import mnist
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib
  from tensorflowonspark_tpu.parallel import sharding

  # columnar feed: batches (and train-loop slabs) assemble from column
  # views, no per-row python loop; sorted mapping keys follow row order
  feed = ctx.get_data_feed(
      train_mode=True, input_mapping={"c0_image": "image",
                                      "c1_label": "label"})
  model = mnist.MLP()
  state = mnist.create_state(jax.random.PRNGKey(args.seed), model=model)
  mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=-1),
                             devices=jax.devices()[:1])

  def loss_fn(params, batch):
    logits = model.apply({"params": params},
                         batch["image"].astype("float32"), train=True)
    return mnist.loss_fn(logits, batch["label"].astype("int32"))

  # unroll defaults to the cluster's train_unroll (TOS_TRAIN_UNROLL):
  # K steps fused into one dispatch, same trajectory as per-step
  loop = sharding.make_train_loop(loss_fn, mesh, donate_state=False)
  for item in device_prefetch(slab_batches(feed, args.batch_size),
                              size=2):
    state, losses = loop(state, item)
    if loop.steps % 20 < len(np.asarray(losses)):
      print("node %d step %d loss %.4f"
            % (ctx.executor_id, loop.steps, float(np.asarray(losses)[-1])))
  print("node %d done after %d steps (unroll=%d)"
        % (ctx.executor_id, loop.steps, loop.unroll))
  if ctx.is_chief and args.export_dir:
    ctx.export_model(state.params, args.export_dir)


if __name__ == "__main__":
  parser = argparse.ArgumentParser()
  parser.add_argument("--executors", type=int, default=2)
  parser.add_argument("--epochs", type=int, default=2)
  parser.add_argument("--batch_size", type=int, default=64)
  parser.add_argument("--num_samples", type=int, default=2048)
  parser.add_argument("--partitions", type=int, default=8)
  parser.add_argument("--seed", type=int, default=0)
  parser.add_argument("--export_dir", default=None)
  parser.add_argument("--unroll", type=int, default=0,
                      help="fuse K optimizer steps per dispatch on every "
                           "node (cluster.run(train_unroll=K); 0 = "
                           "per-step)")
  args = parser.parse_args()

  from tensorflowonspark_tpu import cluster
  from tensorflowonspark_tpu.cluster import InputMode
  from tensorflowonspark_tpu.engine import LocalEngine
  from tensorflowonspark_tpu.models import mnist

  images, labels = mnist.synthetic_dataset(args.num_samples)
  # ndarray image rows + exact-int labels keep the feed columnar end to
  # end (feeder encodes one column chunk; nodes assemble by column views)
  rows = list(zip(images, labels.tolist()))
  partitions = [rows[i::args.partitions] for i in range(args.partitions)]

  engine = LocalEngine(num_executors=args.executors)
  try:
    c = cluster.run(engine, main_fn, tf_args=args,
                    input_mode=InputMode.ENGINE,
                    train_unroll=args.unroll or None)
    c.train(partitions, num_epochs=args.epochs)
    c.shutdown(grace_secs=2)
    print("training complete")
  finally:
    engine.stop()
