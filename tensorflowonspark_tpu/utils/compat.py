"""Version/role compatibility shims for user main functions.

Parity with the reference's ``compat.py``
(/root/reference/tensorflowonspark/compat.py:10-31): the load-bearing behavior
is *chief-only export* — every worker calls ``export_saved_model`` but only the
chief writes to the real destination; non-chiefs write to a throwaway local dir
so collective-dependent export code still runs on all nodes. Here the exported
artifact is an Orbax checkpoint / flax state rather than a TF SavedModel.
"""

import logging
import tempfile

logger = logging.getLogger(__name__)


def jax_shard_map(*args, **kwargs):
  """``jax.shard_map`` (the installed jax, 0.9, exports it at top level
  with ``check_vma``). An alias so the in-repo call sites keep ONE lazy
  import — jax must not be imported at orchestration-layer import time."""
  from jax import shard_map
  return shard_map(*args, **kwargs)


def jax_axis_size(axis_name):
  """``lax.axis_size`` — a static python int inside shard_map bodies,
  usable as a loop bound."""
  from jax import lax
  return lax.axis_size(axis_name)


def export_model(state, export_dir: str, is_chief: bool) -> str:
  """Export model state; chief writes to ``export_dir``, others to a tmp dir.

  Args:
    state: a pytree of arrays (e.g. flax TrainState params) to save.
    export_dir: destination directory for the chief's export.
    is_chief: whether this process is chief/worker:0.

  Returns the directory actually written to.
  """
  import jax
  import numpy as np
  import orbax.checkpoint as ocp
  from tensorflowonspark_tpu.utils import paths

  # numpy SCALAR leaves (np.float32(3.0) — e.g. optimizer counts) are
  # rejected by current orbax; 0-d ndarrays round-trip identically
  state = jax.tree_util.tree_map(
      lambda x: np.asarray(x) if isinstance(x, np.generic) else x, state)
  target = export_dir if is_chief else tempfile.mkdtemp(prefix="nonchief_export_")
  ckptr = ocp.StandardCheckpointer()
  ckptr.save(paths.for_io(paths.join(target, "model")), state, force=True)
  ckptr.wait_until_finished()
  logger.info("exported model to %s (chief=%s)", target, is_chief)
  return target


def import_model(export_dir: str, template=None):
  """Load a model state previously written by :func:`export_model`."""
  import orbax.checkpoint as ocp
  from tensorflowonspark_tpu.utils import paths

  ckptr = ocp.StandardCheckpointer()
  path = paths.for_io(paths.join(export_dir, "model"))
  if template is not None:
    return ckptr.restore(path, template)
  return ckptr.restore(path)


def is_tpu_available() -> bool:
  """Accelerator-availability shim for user code (parity:
  reference compat.is_gpu_available, compat.py:27-31)."""
  from tensorflowonspark_tpu.utils import tpu_info
  return tpu_info.is_tpu_available()


def disable_auto_shard(options) -> None:
  """No-op on the JAX path (parity stub: reference compat.py:20-24).

  The reference disabled tf.data auto-sharding when feeding from Spark; the
  JAX feed plane shards explicitly by executor, so there is nothing to disable.
  """
  logger.debug("disable_auto_shard: no-op on the TPU/JAX path")
