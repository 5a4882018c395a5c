"""L5' ML pipeline: Estimator/Model API over the cluster layer.

Capability parity with the reference's ``pipeline.py``
(/root/reference/tensorflowonspark/pipeline.py), without requiring Spark ML:

- ``Namespace`` + ``TFParams.merge_args_params`` reproduce the layered
  config merge (:299-351);
- the ``Has*`` param mixins exist with the same names and setter/getter
  surface (:52-296), generated over a lightweight Params base;
- ``TFEstimator.fit`` launches a real cluster in ENGINE input mode, feeds
  the dataset sorted by input-mapping columns, shuts down with a grace
  period, and returns a ``TFModel`` (:354-435);
- ``TFModel.transform`` runs independent single-node inference per
  executor with a per-process model cache (:438-647), loading an exported
  bundle (orbax state + cloudpickled predict fn) instead of a TF
  SavedModel signature;
- ``yield_batch`` batches rows for the predict fn (:691-713).

The model artifact is a *bundle* directory:
  ``<export_dir>/model/``     orbax checkpoint of the params pytree
  ``<export_dir>/predict.pkl`` cloudpickled ``predict_fn(params, batch)``
where ``batch`` is a dict of stacked numpy arrays keyed by input tensor
names, and the fn returns a dict keyed by output tensor names.
"""

import argparse
import logging
import os
from typing import Dict, Iterable, List, Optional, Sequence

from tensorflowonspark_tpu import cluster as cluster_lib
from tensorflowonspark_tpu.cluster import InputMode

logger = logging.getLogger(__name__)


class Namespace(dict):
  """argparse-compatible bag of arguments (parity: pipeline.py:299-339).

  Accepts a dict, an ``argparse.Namespace``, a list of argv strings, or
  another Namespace; attribute and item access are interchangeable.
  """

  def __init__(self, d=None):
    super().__init__()
    if d is None:
      return
    if isinstance(d, (list, tuple)):
      self["argv"] = list(d)
    elif isinstance(d, argparse.Namespace):
      self.update(vars(d))
    elif isinstance(d, dict):
      self.update(d)
    else:
      raise TypeError("unsupported Namespace source: %r" % type(d))

  def __getattr__(self, name):
    try:
      return self[name]
    except KeyError:
      raise AttributeError(name)

  def __setattr__(self, name, value):
    self[name] = value


# --- lightweight Spark-ML-style Params --------------------------------------


class Params(object):
  """Minimal Params base: declared params become get/set pairs."""

  _params: Dict[str, object]

  def __init__(self):
    self._params = {}

  def _declare(self, name: str, default=None):
    self._params.setdefault(name, default)

  def _set(self, **kwargs):
    for k, v in kwargs.items():
      self._params[k] = v
    return self

  def _get(self, name: str):
    return self._params.get(name)


def _mixin(name: str, param: str, default=None, cap: Optional[str] = None):
  """Build a Has<X> mixin exposing set<X>/get<X> (parity: the ~17 Has*
  mixins at reference pipeline.py:52-296)."""
  cap = cap or "".join(p.capitalize() for p in param.split("_"))

  def setter(self, value):
    self._declare(param, default)
    return self._set(**{param: value})

  def getter(self):
    self._declare(param, default)
    return self._get(param)

  return type(name, (object,), {"set" + cap: setter, "get" + cap: getter,
                                "_param_name": param,
                                "_param_default": default})


HasBatchSize = _mixin("HasBatchSize", "batch_size", 100)
HasClusterSize = _mixin("HasClusterSize", "cluster_size", 1)
HasNumPS = _mixin("HasNumPS", "num_ps", 0, cap="NumPS")
HasInputMapping = _mixin("HasInputMapping", "input_mapping")
HasOutputMapping = _mixin("HasOutputMapping", "output_mapping")
HasInputMode = _mixin("HasInputMode", "input_mode", InputMode.ENGINE)
HasMasterNode = _mixin("HasMasterNode", "master_node", "chief")
HasModelDir = _mixin("HasModelDir", "model_dir")
HasExportDir = _mixin("HasExportDir", "export_dir")
HasEpochs = _mixin("HasEpochs", "epochs", 1)
HasGraceSecs = _mixin("HasGraceSecs", "grace_secs", 30)
HasReservationTimeout = _mixin("HasReservationTimeout",
                               "reservation_timeout", 600)
HasFeedTimeout = _mixin("HasFeedTimeout", "feed_timeout", 600)
HasTensorboard = _mixin("HasTensorboard", "tensorboard", False)
HasSignatureDefKey = _mixin("HasSignatureDefKey", "signature_def_key",
                            "serving_default")
HasChipsPerNode = _mixin("HasChipsPerNode", "chips_per_node", 0)
HasProtocol = _mixin("HasProtocol", "protocol", "grpc")


class TFParams(Params, HasBatchSize, HasClusterSize, HasNumPS,
               HasInputMapping, HasOutputMapping, HasInputMode,
               HasMasterNode, HasModelDir, HasExportDir, HasEpochs,
               HasGraceSecs, HasReservationTimeout, HasFeedTimeout,
               HasTensorboard, HasSignatureDefKey, HasChipsPerNode,
               HasProtocol):
  """All pipeline params (parity: reference TFParams, pipeline.py:342-351)."""

  def merge_args_params(self, args) -> Namespace:
    """Overlay set params onto a Namespace of args."""
    merged = Namespace(args)
    merged.update(self._params)
    return merged


# --- model bundle -----------------------------------------------------------


def export_bundle(params, predict_fn, export_dir: str,
                  is_chief: bool = True, example_batch=None,
                  output_signature: Optional[Dict] = None) -> str:
  """Write the model bundle (orbax params + pickled predict fn).

  When ``example_batch`` (a dict of input arrays) is given, the predict fn
  runs once at export time and the bundle records an output SIGNATURE —
  output names, dtypes and trailing shapes — so serving derives its output
  schema from the model without the caller re-declaring it
  (parity: Scala ``TFModel.transformSchema`` deriving output columns from
  the graph, reference TFModel.scala:294-311). ``output_signature`` may
  instead declare it explicitly: ``{name: {"dtype": ..., "shape": [...]}}``.
  """
  import cloudpickle
  from tensorflowonspark_tpu.utils import compat

  target = compat.export_model(params, export_dir, is_chief)
  with open(os.path.join(target, "predict.pkl"), "wb") as f:
    cloudpickle.dump(predict_fn, f)

  signature = dict(output_signature) if output_signature else None
  inputs = None
  if example_batch is not None:
    import numpy as np
    inputs = sorted(example_batch)
    out = predict_fn(params, example_batch)
    if not isinstance(out, dict):
      out = {"output": out}
    signature = {
        name: {"dtype": str(np.asarray(a).dtype),
               # leading batch dim is caller-determined; record the rest
               "shape": [None] + list(np.asarray(a).shape[1:])}
        for name, a in out.items()}
  if signature is not None:
    with open(os.path.join(target, "signature.json"), "w") as f:
      import json
      json.dump({"inputs": inputs, "outputs": signature}, f, indent=2)
  return target


def load_signature(export_dir: str) -> Optional[Dict]:
  """The bundle's recorded IO signature, or None for pre-signature
  bundles: ``{"inputs": [names] | None, "outputs": {name: {dtype, shape}}}``.
  """
  path = os.path.join(export_dir, "signature.json")
  if not os.path.exists(path):
    return None
  import json
  with open(path) as f:
    return json.load(f)


def signature_output_names(export_dir: str) -> Optional[List[str]]:
  """The bundle signature's output columns in serving order (sorted), or
  None for pre-signature bundles. The ONE derivation both TFModel.transform
  and the inference CLI use, so column names and value order can never
  drift apart (transformSchema parity, reference TFModel.scala:294-311)."""
  sig = load_signature(export_dir)
  if sig and sig.get("outputs"):
    return sorted(sig["outputs"])
  return None


def _host_local_slot(workers_per_host: int):
  """Claim a free host-local worker slot from a flock'd slot file.

  Spark offers no guarantee that tasks co-located on one host carry
  non-congruent partition ids — ids 0 and ``workers_per_host`` landing on
  the same host would both map to slot 0 under a plain modulus. A per-host
  slot file (``fcntl.flock`` over a tmp path, keyed by uid) hands each
  claiming process a distinct free slot instead, which is disjoint
  whenever at most ``workers_per_host`` executor processes claim per host
  — the sizing the ``chips_per_node`` contract implies. Returns None when
  the slot file is unusable or exhausted; callers fall back to the
  partition-id heuristic.

  The file holds a ``{slot: claiming pid}`` map, not a bare counter:
  claims by dead processes are reclaimed, so a replacement executor after
  a task failure takes the freed slot instead of colliding with a live
  one. When every slot is held by a live process (oversubscription) the
  claim returns None. The open refuses symlinks and the lock wait is
  bounded — a wedged (or hostile) holder on the shared tmp path degrades
  placement to the heuristic, never hangs the task.
  """
  import fcntl
  import json
  import tempfile
  import time
  path = os.path.join(tempfile.gettempdir(),
                      "tos_transform_slots.%d" % os.getuid())
  try:
    fd = os.open(path,
                 os.O_RDWR | os.O_CREAT | getattr(os, "O_NOFOLLOW", 0),
                 0o600)
  except OSError:
    return None
  try:
    for _ in range(50):
      try:
        fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
        break
      except OSError:
        time.sleep(0.1)
    else:
      return None
    try:
      raw = os.read(fd, 1 << 16).strip()
      try:
        claims = {int(s): int(p) for s, p in json.loads(raw).items()} \
            if raw else {}
      except (ValueError, AttributeError):
        claims = {}

      def _alive(pid):
        try:
          os.kill(pid, 0)
          return True
        except OSError:
          return False

      claims = {s: p for s, p in claims.items()
                if 0 <= s < workers_per_host and _alive(p)}
      me = os.getpid()
      for s, p in claims.items():
        if p == me:  # idempotent under worker reuse: keep the held slot
          return s
      free = [s for s in range(workers_per_host) if s not in claims]
      if not free:
        return None
      claims[free[0]] = os.getpid()
      os.lseek(fd, 0, os.SEEK_SET)
      os.ftruncate(fd, 0)
      os.write(fd, json.dumps({str(s): p
                               for s, p in claims.items()}).encode())
      return free[0]
    finally:
      fcntl.flock(fd, fcntl.LOCK_UN)
  except OSError:
    return None
  finally:
    os.close(fd)


def _transform_worker_slot(workers_per_host: int = 0) -> int:
  """This task's host-local worker index for chip placement.

  LocalEngine executors export ``TOS_EXECUTOR_SLOT``. Spark tasks claim
  the next slot from a host-local atomic counter when ``workers_per_host``
  is known (guaranteed-disjoint, see ``_host_local_slot``), falling back
  to a deterministic slot from their partition id (the reference's
  placement-by-worker-index, gpu_info.py:80-91 — a heuristic: congruent
  partition ids co-located on one host would double-claim). Anything else
  gets slot 0.
  """
  slot = os.environ.get("TOS_EXECUTOR_SLOT")
  if slot is not None:
    return int(slot)
  try:
    from pyspark import TaskContext
    ctx = TaskContext.get()
    if ctx is not None:
      if workers_per_host > 0:
        claimed = _host_local_slot(workers_per_host)
        if claimed is not None:
          return claimed
      return ctx.partitionId()
  except ImportError:
    pass
  return 0


def _allocate_transform_chips(chips_per_node: int) -> None:
  """Claim this task's disjoint chip share before JAX initializes.

  No-op without ``chips_per_node``, in test mode, or when already
  allocated; a request with no TPU topology visible raises
  (``tpu_info.claim_chips``).
  """
  if not chips_per_node or os.environ.get("TOS_TPU_TEST_MODE"):
    return
  if os.environ.get("TOS_CHIP_ENV_APPLIED"):
    return  # a prior task on this executor process already claimed chips
  from tensorflowonspark_tpu.utils import tpu_info
  topo = tpu_info.get_topology()
  workers_per_host = max(1, topo.chips_per_host // chips_per_node) \
      if topo is not None else 1
  slot = _transform_worker_slot(workers_per_host) % workers_per_host
  tpu_info.claim_chips(chips_per_node, slot, what="TFModel.transform")
  os.environ["TOS_CHIP_ENV_APPLIED"] = "1"


# per-executor-process bundle cache (parity: pipeline.py:495-499)
_bundle_cache: Dict[str, tuple] = {}


def load_bundle(export_dir: str):
  """Load (params, predict_fn), cached per process."""
  import cloudpickle
  from tensorflowonspark_tpu.utils import compat

  key = os.path.abspath(export_dir)
  if key not in _bundle_cache:
    params = compat.import_model(export_dir)
    with open(os.path.join(export_dir, "predict.pkl"), "rb") as f:
      predict_fn = cloudpickle.load(f)
    _bundle_cache[key] = (params, predict_fn)
    logger.info("loaded model bundle from %s", export_dir)
  return _bundle_cache[key]


def yield_batch(iterable: Iterable, batch_size: int,
                num_tensors: int = 1):
  """Group rows into lists-of-columns batches (parity: pipeline.py:691-713).

  Yields lists of ``num_tensors`` column lists.
  """
  cols: List[List] = [[] for _ in range(num_tensors)]
  count = 0
  for row in iterable:
    if num_tensors == 1 and not isinstance(row, (tuple, list)):
      row = (row,)
    for i in range(num_tensors):
      cols[i].append(row[i])
    count += 1
    if count >= batch_size:
      yield cols
      cols = [[] for _ in range(num_tensors)]
      count = 0
  if count > 0:
    yield cols


# --- Estimator / Model ------------------------------------------------------


class TFEstimator(TFParams):
  """Trains a model on a cluster and produces a TFModel.

  ``train_fn(args, ctx)`` is the user main function; it should consume the
  DataFeed and, on the chief, call ``pipeline.export_bundle`` with
  ``args.export_dir``.
  """

  def __init__(self, train_fn, tf_args=None, export_fn=None):
    super().__init__()
    self.train_fn = train_fn
    self.tf_args = tf_args if tf_args is not None else {}
    self.export_fn = export_fn

  def fit(self, engine, partitions: Sequence) -> "TFModel":
    """Launch a cluster, feed the dataset, return the trained TFModel
    (parity: TFEstimator._fit, pipeline.py:395-435)."""
    args = self.merge_args_params(self.tf_args)
    cluster_size = args.get("cluster_size") or engine.num_executors
    logger.info("fitting TFEstimator on %d executor(s)", cluster_size)

    input_mode = args.get("input_mode", InputMode.ENGINE)
    cluster = cluster_lib.run(
        engine, self.train_fn, tf_args=args,
        num_executors=cluster_size,
        num_ps=args.get("num_ps", 0),
        tensorboard=bool(args.get("tensorboard")),
        input_mode=input_mode,
        log_dir=args.get("model_dir"),
        master_node=args.get("master_node", "chief"),
        reservation_timeout=args.get("reservation_timeout", 600),
        chips_per_node=args.get("chips_per_node", 0))
    if input_mode == InputMode.ENGINE:
      cluster.train(partitions, num_epochs=args.get("epochs", 1),
                    feed_timeout=args.get("feed_timeout", 600))
    # FILES mode: the main fn reads its own data; nothing to feed
    cluster.shutdown(grace_secs=args.get("grace_secs", 30))

    model = TFModel(self.tf_args)
    model._params.update(self._params)
    return model


class TFModel(TFParams):
  """Batch inference with independent per-executor model instances
  (parity: TFModel, pipeline.py:438-647)."""

  def __init__(self, tf_args=None):
    super().__init__()
    self.tf_args = tf_args if tf_args is not None else {}

  def transform(self, engine, partitions: Sequence, collect: bool = True):
    """Run the exported bundle over partitioned rows.

    Rows are tuples ordered by ``sorted(input_mapping)`` columns; outputs
    are tuples ordered by ``sorted(output_mapping)`` tensor names
    (column-mapping parity: pipeline.py:463-492).

    ``collect=False`` returns the engine's lazy handle instead of a
    driver-side list (Spark: the uncollected result RDD — the reference's
    ``TFModel._transform`` returned a DataFrame, pipeline.py:487-492;
    LocalEngine: a streaming generator), for cluster-scale inference.
    """
    args = self.merge_args_params(self.tf_args)
    export_dir = args.get("export_dir") or args.get("model_dir")
    if not export_dir:
      raise ValueError("TFModel requires export_dir (or model_dir)")
    input_mapping = args.get("input_mapping") or {}
    output_mapping = args.get("output_mapping") or {}
    batch_size = args.get("batch_size", 100)
    chips_per_node = args.get("chips_per_node", 0) or 0

    input_tensors = [input_mapping[c] for c in sorted(input_mapping)] \
        if input_mapping else None
    output_tensors = sorted(output_mapping) if output_mapping else None
    if output_tensors is None:
      # transformSchema parity: the bundle's recorded signature declares
      # the output columns ahead of execution (TFModel.scala:294-311)
      output_tensors = signature_output_names(export_dir)

    def _transform_partition(iterator):
      import numpy as np

      def _stack_column(col):
        # variable-length rows (mixed-length generation prompts) cannot
        # stack rectangularly: hand the predict fn an object column —
        # serving predict fns route those through the continuous-batching
        # engine (models.transformer.make_serving_predict_fn)
        try:
          return np.asarray(col)
        except ValueError:
          arr = np.empty(len(col), object)
          arr[:] = col
          return arr

      # N parallel inference tasks on one TPU host must claim DISJOINT
      # chips (the same allocation parallel/runner.py does, parity
      # TFParallel.py:43-56) — before the bundle load initializes JAX
      _allocate_transform_chips(chips_per_node)
      params, predict_fn = load_bundle(export_dir)
      results = []
      n_cols = len(input_tensors) if input_tensors else 1
      for cols in yield_batch(iterator, batch_size, n_cols):
        if input_tensors:
          batch = {name: _stack_column(col)
                   for name, col in zip(input_tensors, cols)}
        else:
          batch = {"input": _stack_column(cols[0])}
        out = predict_fn(params, batch)
        if not isinstance(out, dict):
          out = {"output": out}
        names = output_tensors or sorted(out)
        arrays = [np.asarray(out[n]) for n in names]
        for i in range(len(arrays[0])):
          row = tuple(a[i].tolist() for a in arrays)
          results.append(row[0] if len(row) == 1 else row)
      return results

    if collect:
      return engine.map_partitions(partitions, _transform_partition,
                                   timeout=args.get("feed_timeout", 600))
    return engine.map_partitions_lazy(partitions, _transform_partition,
                                      timeout=args.get("feed_timeout", 600))
