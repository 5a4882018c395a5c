"""MNIST via the Estimator/Model pipeline API.

Parity with the reference's ``examples/mnist/keras/mnist_pipeline.py``:
TFEstimator.fit trains on a cluster fed by the engine, exports a bundle,
and TFModel.transform runs batch inference per executor.

Run:  python examples/mnist/mnist_pipeline.py --executors 2
"""

import argparse
import os
import sys

# allow running straight from a repo checkout (no install needed)
sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir)))


def train_fn(args, ctx):
  import jax
  import numpy as np
  from tensorflowonspark_tpu import pipeline
  from tensorflowonspark_tpu.models import mnist

  feed = ctx.get_data_feed(train_mode=True,
                           input_mapping={"image": "x", "label": "y"})
  state = mnist.create_state(jax.random.PRNGKey(0))
  while not feed.should_stop():
    batch = feed.next_batch(args["batch_size"])
    if not batch["x"]:
      continue
    images = np.asarray(batch["x"], "float32")
    labels = np.asarray(batch["y"], "int32")
    state, _ = mnist.train_step(state, images, labels)

  if ctx.is_chief:
    apply_fn = state.apply_fn

    def predict_fn(params, batch):
      import numpy as np
      logits = apply_fn({"params": params},
                        np.asarray(batch["x"], "float32"))
      return {"label": np.argmax(np.asarray(logits), -1)}

    pipeline.export_bundle(jax.device_get(state.params), predict_fn,
                           args["export_dir"], is_chief=True)


if __name__ == "__main__":
  parser = argparse.ArgumentParser()
  parser.add_argument("--executors", type=int, default=2)
  parser.add_argument("--export_dir", default="/tmp/mnist_export")
  parser.add_argument("--num_samples", type=int, default=2048)
  args = parser.parse_args()

  from tensorflowonspark_tpu.engine import LocalEngine
  from tensorflowonspark_tpu.models import mnist
  from tensorflowonspark_tpu.pipeline import TFEstimator

  images, labels = mnist.synthetic_dataset(args.num_samples)
  rows = list(zip(images.tolist(), labels.tolist()))
  partitions = [rows[i::8] for i in range(8)]

  engine = LocalEngine(num_executors=args.executors)
  try:
    est = TFEstimator(train_fn, {"export_dir": args.export_dir,
                                 "batch_size": 64})
    est.setEpochs(3).setGraceSecs(2).setReservationTimeout(60)
    model = est.fit(engine, partitions)

    model.setExportDir(args.export_dir) \
         .setInputMapping({"image": "x"}) \
         .setOutputMapping({"label": "prediction"})
    test_rows = [(img,) for img, _ in rows[:256]]
    preds = model.transform(engine, [test_rows])
    truth = [lbl for _, lbl in rows[:256]]
    acc = sum(int(p == t) for p, t in zip(preds, truth)) / len(truth)
    print("pipeline inference accuracy: %.3f over %d rows" %
          (acc, len(truth)))
  finally:
    engine.stop()
