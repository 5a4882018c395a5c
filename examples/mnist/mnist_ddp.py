"""MNIST with TRUE distributed data parallelism.

The closest analog of the reference's MultiWorkerMirroredStrategy examples
(`examples/mnist/keras/mnist_spark.py`): the cluster synthesizes
jax.distributed coordinates from its rendezvous, every node joins ONE
process group, batches are globally sharded, and XLA inserts the gradient
all-reduce — so all nodes step in lockstep with identical parameters
(verify: both print the same loss curve).

Run:  python examples/mnist/mnist_ddp.py --executors 2 --steps 40
"""

import argparse
import os
import sys

# allow running straight from a repo checkout (no install needed)
sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir)))


def main_fn(args, ctx):
  import jax
  from jax.sharding import NamedSharding, PartitionSpec as P
  from tensorflowonspark_tpu.models import mnist

  ctx.initialize_distributed()
  mesh = jax.make_mesh((jax.device_count(),), ("data",))
  repl = NamedSharding(mesh, P())
  data_sharding = NamedSharding(mesh, P("data"))

  # identical initial params everywhere (same seed, replicated layout)
  state = jax.jit(lambda: mnist.create_state(jax.random.PRNGKey(0)),
                  out_shardings=repl)()
  images, labels = mnist.synthetic_dataset(args.num_samples,
                                           seed=ctx.process_id)
  bs = args.batch_size
  for step in range(args.steps):
    lo = (step * bs) % max(1, args.num_samples - bs + 1)
    gi = jax.make_array_from_process_local_data(
        data_sharding, images[lo:lo + bs])
    gl = jax.make_array_from_process_local_data(
        data_sharding, labels[lo:lo + bs])
    state, loss = mnist.train_step(state, gi, gl)
    if step % 10 == 0:
      print("node %d step %d loss %.4f (global batch %d)"
            % (ctx.executor_id, step, float(loss),
               bs * jax.process_count()))
  if ctx.is_chief and args.export_dir:
    ctx.export_model(jax.device_get(state.params), args.export_dir)


if __name__ == "__main__":
  parser = argparse.ArgumentParser()
  parser.add_argument("--executors", type=int, default=2)
  parser.add_argument("--steps", type=int, default=40)
  parser.add_argument("--batch_size", type=int, default=64,
                      help="per-process batch; global = this x processes")
  parser.add_argument("--num_samples", type=int, default=2048)
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
    print("distributed training complete")
  finally:
    engine.stop()
