"""Export the MNIST-like dataset to TFRecord files.

Parity with the reference's ``examples/mnist/mnist_data_setup.py``
(tfds → TFRecord export via Spark): writes partitioned TFRecord shards
through the native codec, which mnist_tfrecords-style jobs then read with
``data.readers`` (the environment has no dataset egress, so the images are
the deterministic synthetic set from models.mnist).

Run:  python examples/mnist/mnist_data_setup.py --output /tmp/mnist_tfr
"""

import argparse
import os
import sys

# allow running straight from a repo checkout (no install needed)
sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir)))

if __name__ == "__main__":
  parser = argparse.ArgumentParser()
  parser.add_argument("--output", default="/tmp/mnist_tfrecords")
  parser.add_argument("--num_samples", type=int, default=4096)
  parser.add_argument("--partitions", type=int, default=8)
  parser.add_argument("--executors", type=int, default=0,
                      help="write via engine executors when > 0")
  args = parser.parse_args()

  from tensorflowonspark_tpu.data import dfutil
  from tensorflowonspark_tpu.data.schema import parse_schema
  from tensorflowonspark_tpu.models import mnist

  images, labels = mnist.synthetic_dataset(args.num_samples)
  schema = parse_schema("struct<image:array<float>,label:long>")
  rows = [(img.reshape(-1).tolist(), int(lbl))
          for img, lbl in zip(images, labels)]
  parts = [rows[i::args.partitions] for i in range(args.partitions)]

  engine = None
  try:
    if args.executors:
      from tensorflowonspark_tpu.engine import LocalEngine
      engine = LocalEngine(num_executors=args.executors)
    files = dfutil.save_as_tfrecords(parts, schema, args.output,
                                     engine=engine)
    print("wrote %d shard(s) to %s" % (len(files), args.output))
  finally:
    if engine:
      engine.stop()
