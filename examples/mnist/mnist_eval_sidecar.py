"""MNIST with an evaluator sidecar: train-and-evaluate via checkpoints.

Parity with the reference's Estimator ``train_and_evaluate`` topology
(its ``num_ps``/evaluator role template, TFCluster.py role assembly):
workers train and periodically checkpoint through
``utils.checkpoint.CheckpointManager``; the evaluator node polls the
checkpoint directory, restores each new step, and scores a held-out
shard — completely decoupled from the training feed. ``cluster.run``
places the evaluator via ``eval_node=True``; ``shutdown()`` ends it (the
node's parking loop consumes the driver's control-queue None and flips
the hub state off "running", which the sidecar polls).

Run:  python examples/mnist/mnist_eval_sidecar.py --executors 3
(2 workers + 1 evaluator; LocalEngine — swap in SparkEngine unchanged.)
"""

import argparse
import os
import sys

# allow running straight from a repo checkout (no install needed)
sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir)))


def main_fn(args, ctx):
  import os
  import time
  import jax
  import numpy as np
  from tensorflowonspark_tpu.models import mnist
  from tensorflowonspark_tpu.utils.checkpoint import CheckpointManager

  state = mnist.create_state(jax.random.PRNGKey(args.seed))

  if ctx.job_name == "evaluator":
    # sidecar: poll for new checkpoints, score the held-out shard
    # SAME seed as training: synthetic_dataset's class templates derive
    # from the seed, so a different seed is a different task entirely
    # (scores chance accuracy forever); same-seed draws share templates
    images, labels = mnist.synthetic_dataset(args.eval_samples,
                                             seed=args.seed)
    mgr = CheckpointManager(args.model_dir, save_interval_steps=1)
    seen = -1

    fails = {}

    def _eval(step_num):
      try:
        restored = mgr.restore(state, step=step_num)
      except Exception as e:   # noqa: BLE001 - usually still committing
        fails[step_num] = fails.get(step_num, 0) + 1
        if fails[step_num] in (4, 20):   # persistent: surface, rate-limited
          print("evaluator: restore of step %d failing repeatedly: %r"
                % (step_num, e), flush=True)
        return False
      loss, acc = mnist.eval_step(restored, images, labels)
      line = ("evaluator: step %d loss %.4f accuracy %.3f"
              % (step_num, float(loss), float(acc)))
      print(line, flush=True)
      with open(os.path.join(args.model_dir, "eval_log.txt"), "a") as f:
        f.write(line + "\n")
      return True

    while True:
      # the stop signal for a USER sidecar is the hub STATE flipping off
      # "running" (the node's own foreground loop owns the control queue
      # and consumes the driver's None); check-stop AFTER scoring so the
      # stop iteration still evaluates the final checkpoint
      stop = ctx.hub.get("state") != "running"
      latest = mgr.latest_step(refresh=True)
      if latest is not None and latest != seen and _eval(latest):
        seen = latest
      if stop:
        break
      time.sleep(0.5)
    print("evaluator: stop signal after step %d" % seen, flush=True)
    return

  # workers: train from the engine feed, chief checkpoints periodically
  feed = ctx.get_data_feed(train_mode=True)
  mgr = CheckpointManager(args.model_dir,
                          save_interval_steps=args.save_interval)
  step = 0
  while not feed.should_stop():
    batch = feed.next_batch(args.batch_size)
    if not batch:
      continue
    bx = np.asarray([b[0] for b in batch], "float32")
    by = np.asarray([b[1] for b in batch], "int32")
    state, loss = mnist.train_step(state, bx, by)
    step += 1
    mgr.save(step, state, is_chief=ctx.is_chief)
    if args.step_delay:
      time.sleep(args.step_delay)   # demo pacing: keep training alive
                                    # past the evaluator's cold start
  mgr.wait()
  print("worker %d done after %d steps" % (ctx.executor_id, step))


if __name__ == "__main__":
  parser = argparse.ArgumentParser()
  parser.add_argument("--executors", type=int, default=3)
  parser.add_argument("--batch_size", type=int, default=64)
  parser.add_argument("--num_samples", type=int, default=1024)
  parser.add_argument("--eval_samples", type=int, default=256)
  parser.add_argument("--partitions", type=int, default=4)
  parser.add_argument("--save_interval", type=int, default=5)
  parser.add_argument("--epochs", type=int, default=3)
  parser.add_argument("--step_delay", type=float, default=0.25)
  parser.add_argument("--model_dir", default="/tmp/mnist_eval_sidecar")
  parser.add_argument("--seed", type=int, default=0)
  args = parser.parse_args()

  from tensorflowonspark_tpu import cluster
  from tensorflowonspark_tpu.cluster import InputMode
  from tensorflowonspark_tpu.engine import LocalEngine
  from tensorflowonspark_tpu.models import mnist as mnist_mod

  images, labels = mnist_mod.synthetic_dataset(args.num_samples,
                                               seed=args.seed)
  rows = list(zip(images, labels))
  k = args.partitions
  partitions = [rows[i::k] for i in range(k)]

  engine = LocalEngine(num_executors=args.executors)
  try:
    c = cluster.run(engine, main_fn, tf_args=args,
                    input_mode=InputMode.ENGINE, eval_node=True)
    c.train(partitions, num_epochs=args.epochs)
    c.shutdown(timeout=300)   # also stops the evaluator (hub state)
  finally:
    engine.stop()
