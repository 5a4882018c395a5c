"""Independent-parallel runner: N single-node instances, no cluster.

Capability parity with the reference's ``TFParallel.run``
(/root/reference/tensorflowonspark/TFParallel.py:17-74): run a user fn once
per executor, optionally gang-scheduled under barrier execution with
placement info, with per-worker accelerator allocation — used for
embarrassingly-parallel batch inference
(reference examples/mnist/keras/mnist_inference.py:79).
"""

import logging
import os
from typing import List, Optional

from tensorflowonspark_tpu.engine.base import Engine
from tensorflowonspark_tpu.node import TPUNodeContext
from tensorflowonspark_tpu.utils import tpu_info

logger = logging.getLogger(__name__)


def run(engine: Engine, map_fn, tf_args=None,
        num_tasks: Optional[int] = None, use_barrier: bool = True,
        chips_per_node: int = 0, timeout: Optional[float] = None) -> List:
  """Run ``map_fn(tf_args, ctx)`` on ``num_tasks`` independent executors.

  With ``use_barrier`` the tasks are gang-scheduled and each ctx carries the
  addresses of all gang members (parity: BarrierTaskContext.getTaskInfos,
  TFParallel.py:43-56). Returns the per-task results.
  """
  n = num_tasks if num_tasks is not None else engine.num_executors

  def _task_body(task_id: int, addresses: List[str]):
    # a request that cannot be honoured raises (tpu_info.claim_chips)
    tpu_info.claim_chips(chips_per_node, task_id, what="parallel.run")
    ctx = TPUNodeContext(
        executor_id=task_id, job_name="worker", task_index=task_id,
        cluster_spec={"worker": addresses},
        working_dir=os.getcwd())
    return map_fn(tf_args, ctx)

  if use_barrier:
    def _barrier_task(it, barrier_ctx):
      task_id = next(iter(it))
      return _task_body(task_id, barrier_ctx.get_task_infos())

    return engine.barrier_run(_barrier_task, num_tasks=n, timeout=timeout)

  def _plain_task(it):
    task_id = next(iter(it))
    return _task_body(task_id, [])

  return engine.run_on_executors(_plain_task, num_tasks=n).wait(
      timeout=timeout)
