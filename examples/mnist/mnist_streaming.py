"""MNIST streaming training: unbounded micro-batch feed with graceful stop.

Parity with the reference's
``examples/mnist/estimator/mnist_spark_streaming.py`` (DStream feeding with
a stop_streaming signal): the driver feeds rounds from a stream source;
any process with the cluster's rendezvous address can stop it gracefully
(``rendezvous.Client(addr).request_stop()`` — the stop_streaming analog).

Run:  python examples/mnist/mnist_streaming.py --executors 2 --rounds 5
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
  from tensorflowonspark_tpu.models import mnist

  feed = ctx.get_data_feed(train_mode=True)
  state = mnist.create_state(jax.random.PRNGKey(0))
  steps = 0
  while not feed.should_stop():
    batch = feed.next_batch(args.batch_size)
    if not batch:
      continue
    images = np.asarray([b[0] for b in batch], "float32")
    labels = np.asarray([b[1] for b in batch], "int32")
    state, loss = mnist.train_step(state, images, labels)
    steps += 1
  print("node %d processed %d streamed steps" % (ctx.executor_id, steps))


if __name__ == "__main__":
  parser = argparse.ArgumentParser()
  parser.add_argument("--executors", type=int, default=2)
  parser.add_argument("--rounds", type=int, default=5,
                      help="rounds before the driver sends the stop signal")
  parser.add_argument("--batch_size", type=int, default=32)
  args = parser.parse_args()

  from tensorflowonspark_tpu import cluster
  from tensorflowonspark_tpu.cluster import InputMode
  from tensorflowonspark_tpu.control.rendezvous import Client
  from tensorflowonspark_tpu.engine import LocalEngine
  from tensorflowonspark_tpu.models import mnist

  engine = LocalEngine(num_executors=args.executors)
  try:
    c = cluster.run(engine, main_fn, tf_args=args,
                    input_mode=InputMode.ENGINE)

    def stream():
      round_no = 0
      while True:                      # unbounded source
        images, labels = mnist.synthetic_dataset(256, seed=round_no)
        rows = list(zip(images.tolist(), labels.tolist()))
        round_no += 1
        if round_no >= args.rounds:
          # signal BEFORE yielding the final round: train_stream feeds it,
          # sees the flag, and stops at exactly --rounds rounds.
          # (any process with the rendezvous address can do this)
          Client(tuple(c.server_addr)).request_stop()
        yield [rows[i::4] for i in range(4)]

    rounds = c.train_stream(stream(), feed_timeout=120)
    print("streamed %d rounds; shutting down" % rounds)
    c.shutdown(grace_secs=2)
  finally:
    engine.stop()
