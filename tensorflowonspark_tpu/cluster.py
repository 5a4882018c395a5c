"""L3' cluster lifecycle API: reserve → launch → (feed) → shutdown.

Capability parity with the reference's ``TFCluster.py``
(/root/reference/tensorflowonspark/TFCluster.py), generalized over the engine
abstraction (Spark or the built-in LocalEngine) and re-targeted at JAX/TPU:

- ``run()`` builds the role template mapping job names → executor ids
  (reference :256-271), starts the rendezvous server (:283-285), launches the
  node bring-up job asynchronously so feeding can proceed (:318-336), awaits
  and validates reservations with duplicate detection (:357-372);
- ``train()``/``inference()`` implement the engine-pushes-rows input mode,
  with epochs via dataset replication (parity with epochs-via-RDD.union,
  :90-94);
- ``shutdown()`` is PS-aware, pushes end-of-feed into worker queues via a
  shutdown job (:174-176), remotely stops ps/evaluator nodes through their
  driver-reachable hubs (:186-194), enforces a watchdog timeout (default 3
  days, :136-144) and raises if any node failed (:179-183).
"""

import collections.abc
import contextlib
import logging
import os
import random
import signal
import threading
import time
from typing import Dict, List, Optional, Sequence

from tensorflowonspark_tpu import node as node_mod
from tensorflowonspark_tpu.control import feedhub, rendezvous
from tensorflowonspark_tpu.engine.base import Engine, is_executor_lost
from tensorflowonspark_tpu.obs import metrics as obs_metrics
from tensorflowonspark_tpu.obs import spans as obs_spans

logger = logging.getLogger(__name__)


class ClusterSupervisor(object):
  """Driver-side node babysitter: detect dead nodes, relaunch, requeue.

  Two failure signals are watched:

  - **liveness**: executors whose heartbeats stopped past the missed-beat
    deadline (``rendezvous.Liveness`` — a SIGKILL, OOM kill, or TPU-pod
    preemption stops the beats without any traceback);
  - **engine**: node tasks that died WITH their executor (errors carrying
    the ``ExecutorLost`` marker from ``engine.base``).

  Application exceptions (a user fn raising) are NOT retried — they
  propagate exactly as without supervision; restarting a deterministic
  failure is futile and hides bugs. For restartable failures the recovery
  sequence is:

  1. back off (exponential with full jitter, capped at ``backoff_cap``;
     the attempt budget is ``max_restarts`` per executor);
  2. mark the dead node's hub ``dead`` and drain its undelivered feed
     rows (``datafeed.drain_pending_rows``) so blocked feeders complete
     and no delivered-but-unprocessed data is lost;
  3. relaunch the node task via ``Engine.relaunch_task``, handing the
     restart count to the new node (→ ``ctx.restart_count``; the user fn
     resumes via ``CheckpointManager.restore_or``);
  4. await re-registration, patch ``cluster_info`` in place (feed tasks
     submitted afterwards see the new hub), and refeed the drained rows
     through the engine feed path.

  Recoveries run serially on the supervisor thread — deterministic, and
  the backoff budget bounds total recovery time. ``wait_idle()`` lets
  callers (tests, pre-shutdown hooks) block until no recovery is active.
  """

  def __init__(self, engine: Engine, server: rendezvous.Server,
               node_job, cluster_meta: dict, cluster_info: List[dict],
               engine_ids: Sequence[int], tf_status: dict,
               max_restarts: int = 2, backoff: float = 0.5,
               backoff_cap: float = 5.0):
    self.engine = engine
    self.server = server
    self.node_job = node_job
    self.cluster_meta = cluster_meta
    self.cluster_info = cluster_info
    self.tf_status = tf_status
    self.max_restarts = max_restarts
    self.backoff = backoff
    self.backoff_cap = backoff_cap
    self._eid_task = {eid: i for i, eid in enumerate(engine_ids)}
    self._attempts: Dict[int, int] = {}
    self._given_up: set = set()
    #: executor_id -> completed restart count (observability)
    self.restarts: Dict[int, int] = {}
    #: recovery event log: dicts with executor_id / kind / t (monotonic)
    self.events: List[dict] = []
    self._stop = threading.Event()
    self._idle = threading.Event()
    self._idle.set()
    self._thread: Optional[threading.Thread] = None
    # obs seam: recovery events mirror into driver-side counters
    # (cluster.detected_dead / relaunched / recovered / gave_up /
    # skipped_background) and each recovery records a span
    self._obs_reg = obs_metrics.active()
    self._obs_rec = obs_spans.active()

  def _event(self, kind: str, **fields) -> None:
    # structured payloads (attempt / backoff_s / group / ...) mirror the
    # fleet's eject/failover events: obs_report --alerts post-mortems can
    # reconstruct a recovery or resize from the driver JSONL alone
    self.events.append(dict(fields, kind=kind, t=time.monotonic()))
    if self._obs_reg is not None:
      self._obs_reg.counter("cluster." + kind.replace("-", "_")).inc()
    if self._obs_rec is not None:
      self._obs_rec.event("cluster." + kind,
                          **{k: v for k, v in fields.items()
                             if isinstance(v, (int, float, str, bool))})

  def _group_of(self, eid: int):
    """The mesh group this executor hosts (cluster_meta ``group_map``),
    or None for ungrouped clusters. Keys tolerate str/int (the map may
    round-trip through JSON)."""
    gm = self.cluster_meta.get("group_map") or {}
    g = gm.get(eid, gm.get(str(eid)))
    return int(g) if g is not None else None

  # -- lifecycle -------------------------------------------------------------

  def start(self) -> "ClusterSupervisor":
    self._thread = threading.Thread(target=self._loop, daemon=True,
                                    name="cluster-supervisor")
    self._thread.start()
    return self

  def stop(self) -> None:
    self._stop.set()
    if self._thread is not None:
      self._thread.join(timeout=30)

  def wait_idle(self, timeout: float = 60.0) -> bool:
    """Block until no recovery is in flight AND no failure is pending
    detection right now; True if idle within ``timeout``."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
      if self._idle.is_set() and not self._failed_executors():
        return True
      time.sleep(0.05)
    return False

  # -- detection -------------------------------------------------------------

  def _failed_executors(self) -> List[int]:
    if self.server.done.is_set():
      # the rendezvous server stopped serving (streaming stop / shutdown):
      # beats — including goodbyes — can no longer arrive, so silence is
      # not death; report nothing (mirrors the _loop stand-down, and keeps
      # wait_idle from stalling shutdown over phantom deaths)
      return []
    failed = set(self.server.liveness.dead())
    for task_id, err in enumerate(self.node_job.errors):
      if is_executor_lost(err):
        for eid, t in self._eid_task.items():
          if t == task_id:
            failed.add(eid)
    return sorted(e for e in failed
                  if e in self._eid_task and e not in self._given_up)

  def _loop(self) -> None:
    interval = self.cluster_meta.get("heartbeat_interval") or 5.0
    poll = max(0.05, min(1.0, interval / 4.0))
    while not self._stop.wait(poll):
      if self.server.done.is_set():
        # the rendezvous server stopped serving (streaming stop signal /
        # shutdown): heartbeats can no longer arrive, so silence is not
        # death — stand down instead of relaunching healthy nodes
        continue
      for eid in self._failed_executors():
        if self._stop.is_set():
          return
        self._idle.clear()
        try:
          if self._obs_rec is not None:
            with self._obs_rec.span("cluster.recover", executor_id=eid):
              self._recover(eid)
          else:
            self._recover(eid)
        except Exception:  # noqa: BLE001 - supervisor must survive anything
          logger.exception("recovery of executor %d failed", eid)
        finally:
          self._idle.set()

  # -- recovery --------------------------------------------------------------

  def _recover(self, eid: int) -> None:
    attempt = self._attempts.get(eid, 0)
    group = self._group_of(eid)
    self._event("detected-dead", executor_id=eid, attempt=attempt,
                group=group)
    try:
      job_name, _ = node_mod._role_of(eid, self.cluster_meta["cluster_template"])
    except ValueError:
      job_name = "worker"
    if job_name in node_mod.BACKGROUND_ROLES:
      # ps/evaluator bring-up tasks park on the hub control queue for the
      # cluster's whole life — a pinned relaunch could never schedule
      # behind the (healthy) foreground owner, and the replacement would
      # park on a fresh control queue shutdown never signals. Surface the
      # death instead of restarting (parity: the reference reported ps
      # failures at shutdown; supervised restart covers the JAX roles).
      self._given_up.add(eid)
      msg = ("%s node on executor %d died (background-role nodes are not "
             "relaunched; failure will surface at shutdown)"
             % (job_name, eid))
      logger.error(msg)
      self._event("skipped-background", executor_id=eid, group=group)
      if self.tf_status.get("error") is None:
        self.tf_status["error"] = msg
      return
    if attempt >= self.max_restarts:
      self._given_up.add(eid)
      if group is not None and self.cluster_meta.get("elastic"):
        # elastic mode: a grouped executor past its restart budget is a
        # RESIZE, not a job failure — commit the shrink on the sync plane
        # so surviving groups stop waiting for it (parallel.groups)
        self._commit_shrink(eid, group, attempt)
        return
      msg = ("executor %d declared dead after %d restart attempt(s); "
             "restart budget (max_restarts=%d) exhausted"
             % (eid, attempt, self.max_restarts))
      logger.error(msg)
      self._event("gave-up", executor_id=eid, attempts=attempt,
                  group=group)
      # the node task may have completed OK long ago (ENGINE mode: the
      # bring-up task returns before the background fn dies) — make sure
      # shutdown still raises
      if self.tf_status.get("error") is None:
        self.tf_status["error"] = msg
      return
    self._attempts[eid] = attempt + 1
    self.server.liveness.mark_restarting(eid)
    # exponential backoff with full jitter, hard-capped: no recovery-path
    # sleep ever exceeds backoff_cap
    delay = min(self.backoff_cap, self.backoff * (2 ** attempt))
    delay *= 0.5 + random.random()
    if self._stop.wait(min(delay, self.backoff_cap)):
      return

    old_meta = next((n for n in self.cluster_info
                     if n["executor_id"] == eid), None)
    pending = self._quarantine_dead_hub(old_meta)

    task_id = self._eid_task[eid]
    if not self.node_job._completed[task_id]:
      # the node task never finished — a hung user fn (liveness-dead but
      # process alive) would keep its executor busy forever and a pinned
      # relaunch could never schedule; kill the executor so the engine
      # fails the attempt and recycles the slot first
      if self.engine.preempt_task(self.node_job, task_id):
        deadline = time.monotonic() + 10
        while not self.node_job._completed[task_id] \
            and time.monotonic() < deadline and not self._stop.is_set():
          time.sleep(0.05)
    logger.warning("relaunching node on executor %d (attempt %d/%d, "
                   "%d feed row(s) requeued)", eid, attempt + 1,
                   self.max_restarts, sum(map(len, pending.values())))
    self.engine.relaunch_task(self.node_job, task_id,
                              payload={"executor_id": eid,
                                       "restart": attempt + 1})
    # re-arm the startup grace from the relaunch instant: a stale beat
    # from the OLD incarnation clears the restarting flag, and without a
    # fresh grace the next sweep would re-declare death mid-bring-up and
    # burn a second restart attempt on the same failure
    self.server.liveness.rearm(eid)
    self._event("relaunched", executor_id=eid, attempt=attempt + 1,
                backoff_s=round(delay, 3), group=group)

    reregistered = self._await_reregistration(eid, attempt + 1)
    if reregistered:
      self.restarts[eid] = attempt + 1
      self._event("recovered", executor_id=eid, attempt=attempt + 1,
                  group=group)
    else:
      # liveness/ExecutorLost will re-fire and consume another attempt,
      # or the task error (a non-restartable bring-up failure) propagates
      logger.warning("executor %d did not re-register after relaunch", eid)
    if pending:
      # refeed regardless of the relaunch outcome: the rescued rows go to
      # whichever LIVE worker picks up the feed task, so a slow relaunch
      # must not drop them
      self._refeed(pending)

  def _commit_shrink(self, eid: int, group: int, attempts: int) -> None:
    """Elastic resize, shrink direction: evict the dead executor's group
    from the sync plane so rounds never wait for it and its stale
    contributions are rejected; training continues on the survivors with
    the sync denominator reduced. Only an empty group set is fatal."""
    plane = getattr(self.server, "sync_plane", None)
    active = None
    if plane is not None:
      plane.mark_lost(group, "executor %d dead past restart budget "
                      "(%d attempt(s))" % (eid, attempts))
      active = plane.status()["groups_active"]
    logger.error("executor %d (group %d) declared dead after %d restart "
                 "attempt(s); committing the shrink — %s group(s) remain",
                 eid, group, attempts, active)
    self._event("resize-shrink", executor_id=eid, group=group,
                attempts=attempts, groups_active=active)
    if active == 0 and self.tf_status.get("error") is None:
      self.tf_status["error"] = (
          "all training groups lost (last: group %d on executor %d)"
          % (group, eid))

  def readmit(self, eid: int) -> None:
    """Elastic resize, grow/re-admit direction: the engine brought the
    executor's capacity back (or an operator re-added it) after the
    supervisor gave up on it. The restart budget resets and liveness
    re-arms its startup grace so the rebooting node isn't re-declared
    dead mid-bring-up; the node's group rejoins the sync plane itself
    (``GroupSyncClient.join`` pulls the catch-up weights) at its next
    sync boundary."""
    self._given_up.discard(eid)
    self._attempts.pop(eid, None)
    self.server.liveness.rearm(eid)
    self._event("resize-readmit", executor_id=eid,
                group=self._group_of(eid))

  def _quarantine_dead_hub(self, old_meta: Optional[dict]) -> Dict[str, List]:
    """Mark the dead node's hub unusable and rescue undelivered feed rows.

    The hub manager is a separate process and routinely survives its
    node's death; marking it ``dead`` makes the relaunched node's reclaim
    check (node.py) treat it as stale, and the drain releases feeders
    blocked on ``queue.join``. Best-effort: an unreachable hub (true for
    remote workers' loopback hubs) just means nothing to rescue.
    """
    if old_meta is None:
      return {}
    try:
      hub = feedhub.connect(tuple(old_meta["hub_addr"]),
                            self.cluster_meta["authkey"])
      hub.set("state", "dead")
    except Exception:  # noqa: BLE001 - hub died with the node
      return {}
    pending: Dict[str, List] = {}
    if self.cluster_meta.get("input_mode") == InputMode.ENGINE:
      from tensorflowonspark_tpu.datafeed import drain_pending_rows
      # inference feeds need their EndPartition markers preserved in
      # stream order across the refeed, or per-partition result alignment
      # is lost (TPUCluster.inference stamps feed_kind on the shared meta)
      keep_markers = self.cluster_meta.get("feed_kind") == "inference"
      # every DATA queue, not just the default: train/inference accept a
      # custom qname and those rows (and their blocked feeders) need the
      # drain just as much
      for qname in self.cluster_meta.get("queues", ("input",)):
        if qname in ("error", "output", "control"):
          continue
        try:
          rows = drain_pending_rows(hub, qname, keep_markers=keep_markers)
        except Exception:  # noqa: BLE001 - manager vanished mid-drain
          logger.warning("draining queue %r of executor %d's dead hub "
                         "failed", qname, old_meta["executor_id"])
          continue
        if rows:
          pending[qname] = rows
    return pending

  def _await_reregistration(self, eid: int, generation: int,
                            timeout: float = 120.0) -> bool:
    """Poll the reservation table until the relaunched node registered its
    restart ``generation``; patch cluster_info in place on success. (The
    pid alone can't identify the new incarnation: an ENGINE-mode relaunch
    runs in the same executor process as its predecessor.)"""
    deadline = time.monotonic() + min(
        timeout, self.cluster_meta.get("reservation_timeout", timeout))
    while time.monotonic() < deadline and not self._stop.is_set():
      for n in self.server.reservations.get():
        if n["executor_id"] == eid and n.get("restart") == generation:
          for meta in self.cluster_info:
            if meta["executor_id"] == eid:
              meta.update(n)
          return True
      # a relaunch that failed bring-up for an application reason (not an
      # executor loss) will never register — stop waiting and let the
      # task error propagate
      err = self.node_job.errors[self._eid_task[eid]]
      if err is not None and not is_executor_lost(err):
        return False
      time.sleep(0.05)
    return False

  def _refeed(self, pending: Dict[str, List]) -> None:
    """Requeue rescued feed rows through the engine feed path — one feed
    task per drained queue, back into the SAME qname: they land on
    whichever live worker picks the task up (at-least-once delivery for
    rows the dead worker never processed)."""
    for qname, rows in pending.items():
      fn = node_mod.make_train_fn(self.cluster_info, self.cluster_meta,
                                  qname=qname)
      try:
        self.engine.foreach_partition([rows], fn).wait(timeout=120)
        logger.info("requeued %d feed row(s) into %r from the dead node",
                    len(rows), qname)
      except Exception as e:  # noqa: BLE001 - best-effort; loss is logged
        logger.error("requeueing %d rescued feed row(s) into %r failed: %s",
                     len(rows), qname, e)


def _driver_obs_log(recorder=None):
  """The driver's per-process obs JSONL (anchored by the recorder's
  clock when one is live) — shared between the detector's per-alert
  appends and the shutdown span/metrics dump."""
  from tensorflowonspark_tpu.obs import export as obs_export
  return obs_export.ProcessLog(
      label="driver", executor_id=0,
      clock=recorder.clock if recorder is not None else None)


class InputMode(object):
  """How the cluster gets training data (parity: TFCluster.py:43-46).

  ``FILES`` (alias ``TENSORFLOW``): each node reads its own data shard
  (grain / tf.data / raw files from GCS or local disk); the engine only holds
  the executor slots.

  ``ENGINE`` (alias ``SPARK``): the engine pushes partitioned rows into each
  node's feed hub, consumed by the user fn through a DataFeed.
  """
  FILES = 0
  TENSORFLOW = 0
  ENGINE = 1
  SPARK = 1


class _StreamFeedHandle(object):
  """Progress of a hooked (D)Stream feed: micro-batches fed + stop flag."""

  def __init__(self):
    self.rounds = 0
    self.stopped = False


class TPUCluster(object):
  """Handle for a started cluster (parity: TFCluster.py:49-212)."""

  def __init__(self, engine: Engine, cluster_info: List[dict],
               cluster_meta: dict, server: rendezvous.Server,
               input_mode: int, node_job, tf_status: dict,
               driver_ps_procs: Sequence = (), supervisor=None,
               detector=None):
    self.engine = engine
    self.cluster_info = cluster_info
    self.cluster_meta = cluster_meta
    self.server = server
    self.input_mode = input_mode
    self.node_job = node_job
    self.tf_status = tf_status
    self.queues = cluster_meta["queues"]
    self.driver_ps_procs = list(driver_ps_procs)
    self.supervisor = supervisor
    #: the driver-side obs aggregation (obs.collector.ObsSink) when the
    #: obs plane is on (TOS_OBS=1) — executors ship metric/span deltas
    #: here through the rendezvous OBS verb; None when off. getattr:
    #: tests (and embedders) hand in stand-in servers without the field
    self.obs_sink = getattr(server, "obs_sink", None)
    #: the driver-side detector loop (obs.anomaly.AnomalyDetector)
    #: evaluating the sink online; None when the plane (or the detector,
    #: TOS_OBS_DETECT=0) is off
    self.detector = detector

  def alerts(self, max_items: int = 64) -> List[dict]:
    """Newest-first structured alerts from the online detector loop
    (empty when the obs plane / detector is off)."""
    if self.detector is None:
      return []
    return self.detector.recent_alerts(max_items)

  def obs_summary(self) -> dict:
    """The in-process equivalent of the HEALTH verb's obs payload:
    liveness snapshot + per-executor metric state + live alerts + SLO
    status — the driver summary ``tools/obs_top.py`` renders when
    embedded."""
    out = {"data": {str(k): v for k, v in
                    self.server.liveness.snapshot().items()}}
    if self.obs_sink is not None:
      out["obs"] = self.obs_sink.top_summary()
    if self.detector is not None:
      out["alerts"] = self.detector.recent_alerts()
      slo = self.detector.slo_status()
      if slo is not None:
        out["slo"] = slo
      dep = self.detector.deploy_status()
      if dep is not None:
        out["deploy"] = dep
    return out

  def slo_status(self) -> Optional[dict]:
    """Live SLO burn-rate verdicts (``obs.slo``; None when the obs
    plane/detector is off or no objectives are declared) — the
    driver-side read the train→serve canary phase consumes."""
    if self.detector is None:
      return None
    return self.detector.slo_status()

  def deploy_status(self) -> Optional[dict]:
    """Live continuous-deployment state (``serving.deploy`` gauges as
    sampled by the detector; None when the obs plane/detector is off or
    no controller has shipped ``deploy.*`` yet) — which version serves,
    which candidate is canarying, how many rollbacks."""
    if self.detector is None:
      return None
    return self.detector.deploy_status()

  @staticmethod
  def _span(name: str, **attrs):
    """Driver-side span, or a null context when the obs plane is off."""
    rec = obs_spans.active()
    if rec is None:
      return contextlib.nullcontext()
    return rec.span(name, **attrs)

  # -- data plane ------------------------------------------------------------

  def train(self, data_partitions: Sequence, num_epochs: int = 0,
            feed_timeout: float = 600, qname: str = "input"):
    """Feed partitioned data to the cluster (ENGINE input mode only).

    Epochs are implemented by replicating the dataset ``num_epochs`` times
    (parity with epochs-via-RDD.union, reference TFCluster.py:90-94).
    Returns None for bounded data; a DStream argument returns the stream
    feed handle from :meth:`train_dstream`.
    """
    if hasattr(data_partitions, "foreachRDD"):
      # a Spark DStream handed straight to train(), exactly as the
      # reference accepted (TFCluster.py:83-85); the handle exposes
      # rounds-fed / stop-observed progress
      return self.train_dstream(data_partitions, feed_timeout=feed_timeout,
                                qname=qname)
    logger.info("feeding training data")
    assert self.input_mode == InputMode.ENGINE, \
        "train() requires InputMode.ENGINE/SPARK"
    self.cluster_meta["feed_kind"] = "train"
    epochs = max(1, num_epochs)
    parts = self._wrap_lazy(data_partitions)
    fn = node_mod.make_train_fn(self.cluster_info, self.cluster_meta,
                                feed_timeout=feed_timeout, qname=qname)
    if isinstance(parts, collections.abc.Iterator):
      # one-shot partition streams cannot be replayed (and _replicate's
      # fallback would drain the generator eagerly on the driver, feeding
      # epoch 1 and silently starving epochs 2..N), so route them through
      # the engine's lazy path. On LocalEngine the driver holds one window
      # of partitions in flight, never the whole dataset; SparkEngine's
      # _as_rdd still drains the stream into a driver-side list of
      # partition HANDLES before parallelize — O(dataset) only if the
      # stream carries raw rows instead of callables (use lazy handles or
      # train_dstream for big data on Spark)
      if epochs > 1:
        raise ValueError(
            "train(num_epochs=%d) got a one-shot partition iterator; "
            "re-iterable input (a list, an RDD, or lazy handles) is "
            "required to replay epochs" % epochs)
      stream = self.engine.map_partitions_lazy(parts, fn,
                                               timeout=feed_timeout)
      if isinstance(stream, collections.abc.Iterator):
        for _ in stream:   # windowed: one window in flight on the driver
          pass
      else:
        # RDD-like lazy result (SparkEngine hands back an uncollected
        # RDD): trigger the feed with a row-free action — count() runs
        # the tasks distributed and returns only a number
        stream.count()
      return
    parts = self._replicate(parts, epochs)
    with self._span("cluster.train_feed", epochs=epochs):
      self.engine.foreach_partition(parts, fn).wait()

  def train_stream(self, batch_stream, feed_timeout: float = 600,
                   qname: str = "input") -> int:
    """Feed an unbounded stream of partitioned datasets (micro-batches).

    The analog of the reference's Spark Streaming support
    (DStream.foreachRDD feeding, TFCluster.py:83-85): each item of
    ``batch_stream`` is a list of partitions fed as one round. A graceful
    stop request (``request_stop()``, or a remote
    ``rendezvous.Client(addr).request_stop()`` — parity with
    examples/utils/stop_streaming.py) ends the loop after the current
    round. Returns the number of rounds fed.
    """
    assert self.input_mode == InputMode.ENGINE, \
        "train_stream() requires InputMode.ENGINE/SPARK"
    rounds = 0
    for partitions in batch_stream:
      # feed first, check after: a batch already pulled from the source is
      # never discarded (sources may commit offsets on yield)
      self.train(partitions, num_epochs=1, feed_timeout=feed_timeout,
                 qname=qname)
      rounds += 1
      if self.server.stopping():
        logger.info("stop signal received; ending stream after %d rounds",
                    rounds)
        break
    return rounds

  def train_dstream(self, dstream, feed_timeout: float = 600,
                    qname: str = "input"):
    """Hook a Spark (D)Stream so every micro-batch RDD is fed as one round
    (parity: reference TFCluster.train wiring ``dataRDD.foreachRDD(_train)``,
    TFCluster.py:83-85).

    Feeding happens on Spark's streaming driver thread as batches arrive.
    After a graceful stop request (``request_stop()``, or a remote
    ``rendezvous.Client(addr).request_stop()`` — parity with
    examples/utils/stop_streaming.py) later micro-batches are skipped
    without being consumed, so the streaming job can be stopped and
    ``shutdown()`` called. Returns a handle whose ``rounds`` attribute
    counts the micro-batches fed so far and whose ``stopped`` flag reports
    whether the stop signal has been observed.
    """
    assert self.input_mode == InputMode.ENGINE, \
        "train_dstream() requires InputMode.ENGINE/SPARK"
    self.cluster_meta["feed_kind"] = "train"
    fn = node_mod.make_train_fn(self.cluster_info, self.cluster_meta,
                                feed_timeout=feed_timeout, qname=qname)
    handle = _StreamFeedHandle()

    def _feed(rdd):
      if self.server.stopping():
        if not handle.stopped:
          logger.info("stop signal received; skipping further micro-batches "
                      "after %d rounds", handle.rounds)
        handle.stopped = True
        return
      self.engine.foreach_partition(rdd, fn).wait()
      handle.rounds += 1

    dstream.foreachRDD(_feed)
    return handle

  def foreach_batch(self, feed_timeout: float = 600, qname: str = "input"):
    """A ``(batch_df, batch_id) -> None`` callback for Structured Streaming:
    ``query = df.writeStream.foreachBatch(cluster.foreach_batch()).start()``.

    The modern equivalent of the DStream hook above: each micro-batch
    DataFrame is fed as one round; after a stop request batches are
    skipped. The reference predates Structured Streaming — this is the
    same capability on the current Spark API.
    """
    assert self.input_mode == InputMode.ENGINE, \
        "foreach_batch() requires InputMode.ENGINE/SPARK"
    self.cluster_meta["feed_kind"] = "train"
    fn = node_mod.make_train_fn(self.cluster_info, self.cluster_meta,
                                feed_timeout=feed_timeout, qname=qname)

    def _feed(batch_df, batch_id):
      if self.server.stopping():
        return
      self.engine.foreach_partition(batch_df, fn).wait()

    return _feed

  def request_stop(self) -> None:
    """Signal streaming feeds to stop after the current round.

    Sets the server's stop-REQUESTED flag only: the rendezvous keeps
    serving (bring-up polls, heartbeats, goodbyes) until ``shutdown()``
    actually stops it."""
    self.server.stop_requested.set()

  @property
  def server_addr(self):
    """Rendezvous address — remote processes can send the streaming stop
    signal here via ``rendezvous.Client(addr).request_stop()``."""
    return self.server.addr

  def inference(self, data_partitions: Sequence, feed_timeout: float = 600,
                qname: str = "input", collect: bool = True):
    """Feed data for inference (parity: TFCluster.inference, reference
    TFCluster.py:96-115).

    With ``collect=True`` (default) results are gathered into a driver-side
    list — fine for small jobs. With ``collect=False`` the return value is
    the engine's lazy handle (Spark: the uncollected result RDD, exactly
    like the reference; LocalEngine: a streaming generator holding at most
    one window of partitions), so cluster-scale inference output never
    materializes on the driver.
    """
    logger.info("feeding inference data")
    assert self.input_mode == InputMode.ENGINE, \
        "inference() requires InputMode.ENGINE/SPARK"
    # recovery drains must keep EndPartition markers for inference feeds
    # (ClusterSupervisor._quarantine_dead_hub reads this off the shared meta)
    self.cluster_meta["feed_kind"] = "inference"
    fn = node_mod.make_inference_fn(self.cluster_info, self.cluster_meta,
                                    feed_timeout=feed_timeout, qname=qname)
    data_partitions = self._wrap_lazy(data_partitions)
    if collect:
      with self._span("cluster.inference_feed"):
        return self.engine.map_partitions(data_partitions, fn)
    return self.engine.map_partitions_lazy(data_partitions, fn,
                                           timeout=feed_timeout)

  # -- lifecycle -------------------------------------------------------------

  def shutdown(self, grace_secs: float = 0, timeout: int = 259200) -> None:
    """Stop the cluster; raise if any node failed.

    ``timeout`` arms a SIGALRM watchdog (3-day default) guarding against
    hung shutdowns (parity: TFCluster.py:117,136-144).
    """
    in_main = threading.current_thread() is threading.main_thread()
    if timeout and in_main:
      def _watchdog(signum, frame):
        raise TimeoutError("cluster shutdown watchdog fired after %ds" % timeout)
      old = signal.signal(signal.SIGALRM, _watchdog)
      signal.alarm(int(timeout))
    try:
      with self._span("cluster.shutdown"):
        self._shutdown_inner(grace_secs)
    finally:
      if timeout and in_main:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
      # offline-log plane: the driver's own spans + metrics land in the
      # same per-process JSONL scheme the executors use, so
      # tools/obs_report.py merges one run from one directory
      self._dump_driver_obs_log()

  def _dump_driver_obs_log(self) -> None:
    if not obs_metrics.enabled():
      return
    rec = obs_spans.active()
    reg = obs_metrics.active()
    # reuse the detector's log when one exists: same file, ONE meta header
    log = self.detector.jsonl if self.detector is not None \
        and self.detector.jsonl is not None else _driver_obs_log(rec)
    if rec is not None:
      log.append_spans(rec.drain(None))
    log.close(metrics_snapshot=reg.snapshot() if reg is not None else None)

  def _shutdown_inner(self, grace_secs: float) -> None:
    workers = [n for n in self.cluster_info
               if n["job_name"] in node_mod.JAX_ROLES]
    background = [n for n in self.cluster_info
                  if n["job_name"] in node_mod.BACKGROUND_ROLES]

    if self.input_mode == InputMode.ENGINE:
      # push end-of-feed markers through a shutdown job on free (worker)
      # executors (parity: TFCluster.py:174-176)
      fn = node_mod.make_shutdown_fn(
          self.cluster_info, self.cluster_meta, grace_secs=grace_secs,
          queues=[q for q in self.queues if q not in ("error", "output",
                                                      "control")])
      self.engine.foreach_partition([[n["executor_id"]] for n in workers],
                                    fn).wait()
    elif any(n.get("tb_url") for n in self.cluster_info):
      # FILES mode has no feed-shutdown job; still reap the TensorBoard the
      # chief spawned. One PINNED task per executor slot (shared-queue tasks
      # could all land on one free executor and miss the chief's), each
      # best-effort so a dead node can't abort the rest of shutdown.
      fn = node_mod.make_tb_kill_fn(self.cluster_info, self.cluster_meta)
      try:
        self.engine.run_on_executors(
            fn, num_tasks=self.engine.num_executors).wait(
                raise_on_error=False)
      except Exception as e:  # noqa: BLE001 - reap is best-effort
        logger.warning("tensorboard reap job failed: %s", e)

    # stop ps/evaluator nodes by reaching their remote hubs directly
    # (parity: TFCluster.py:186-194)
    for n in background:
      try:
        hub = feedhub.connect(tuple(n["hub_addr"]),
                              self.cluster_meta["authkey"])
        hub.get_queue("control").put(None, block=True, timeout=30)
      except Exception as e:  # noqa: BLE001 - best-effort stop of sidecars
        logger.warning("failed to stop %s:%d: %s", n["job_name"],
                       n["task_index"], e)

    # driver-hosted ps processes exit once their control queue gets None
    for p in self.driver_ps_procs:
      p.join(timeout=60)
      if p.is_alive():
        logger.warning("driver ps process %s did not exit; terminating",
                       p.name)
        p.terminate()

    # wait for the node bring-up job itself (foreground workers return when
    # the user fn finishes); propagate node errors. The supervisor stays
    # live until the job settles: a node death racing shutdown un-completes
    # the job while its recovery runs, so drain recoveries (budget-bounded)
    # and re-wait until the job is stably done, THEN stand the supervisor
    # down before errors are read.
    self.node_job.wait(raise_on_error=False)
    if self.supervisor is not None:
      while True:
        settled = self.supervisor.wait_idle(timeout=120)
        if not settled:
          # a recovery is still in flight after the drain budget: stopping
          # the supervisor now interrupts it (the restarted task's error
          # slot was cleared), so record the situation rather than letting
          # shutdown report success over an unrecovered death
          if self.tf_status.get("error") is None:
            self.tf_status["error"] = (
                "shutdown proceeded while a node recovery was still in "
                "flight (supervisor busy past the drain budget)")
          break
        if self.node_job.done():
          break
        self.node_job.wait(raise_on_error=False)
      self.supervisor.stop()
    if self.detector is not None:
      # stand the loop down FIRST (stop joins the thread), then one last
      # pass so late-arriving deltas (executors final-flush on exit) are
      # evaluated — the other order races the thread's own poll
      self.detector.stop()
      self.detector.poll()
    self.server.stop()
    err = self.node_job.first_error() or self.tf_status.get("error")
    if err:
      raise RuntimeError("cluster shutdown with node error:\n%s" % err)
    logger.info("cluster shutdown complete")

  def tensorboard_url(self) -> Optional[str]:
    """URL of the TensorBoard server, if one was launched (parity:
    TFCluster.tensorboard_url, TFCluster.py:207-212)."""
    for n in self.cluster_info:
      if n.get("tb_url"):
        return n["tb_url"]
    return None

  @staticmethod
  def _wrap_lazy(parts):
    """Bare-callable partitions (lazy handles, e.g. from
    ``load_tfrecords(lazy=True)``) become single-item partitions the
    feeders resolve executor-side (node._materialize_partition).
    Engine-native handles and row partitions pass through untouched."""
    if hasattr(parts, "mapPartitions") or hasattr(parts, "rdd") \
        or hasattr(parts, "foreachRDD"):
      return parts
    if isinstance(parts, collections.abc.Iterator):
      # a one-shot stream of partitions (the collect=False windowed path)
      # must stay a stream — the driver pulls one window at a time
      return ([p] if callable(p) else p for p in parts)
    # any re-iterable collection wraps eagerly (epoch replication
    # re-iterates it)
    return [[p] if callable(p) else p for p in parts]

  @staticmethod
  def _replicate(parts: Sequence, epochs: int):
    """Repeat the dataset ``epochs`` times without touching its rows.

    Engine-native handles (an RDD, or a DataFrame wrapping one) replicate
    via ``union`` — the reference's epochs idiom (``sc.union([rdd]*N)``,
    TFCluster.py:90-94) — so the driver never iterates cluster data.
    Driver-side partition lists are simply concatenated.
    """
    if hasattr(parts, "rdd"):           # DataFrame → its RDD
      parts = parts.rdd
    if hasattr(parts, "mapPartitions"):  # RDD-like: epochs via union
      out = parts
      for _ in range(epochs - 1):
        out = out.union(parts)
      return out
    out = []
    for _ in range(epochs):
      out.extend(parts)
    return out


def run(engine: Engine, main_fn, tf_args=None,
        num_executors: Optional[int] = None, num_ps: int = 0,
        tensorboard: bool = False, input_mode: int = InputMode.FILES,
        log_dir: Optional[str] = None, driver_ps_nodes: bool = False,
        master_node: Optional[str] = None,
        reservation_timeout: float = 600,
        queues: Sequence[str] = ("input", "output", "error", "control"),
        eval_node: bool = False, release_port: bool = True,
        chips_per_node: int = 0, qmax: int = 1024,
        feed_transport: str = "auto", feed_chunk_size: int = 256,
        shm_capacity: int = 64 * 1024 * 1024,
        heartbeat_interval: Optional[float] = 5.0,
        supervise: bool = True, max_restarts: int = 2,
        restart_backoff: float = 0.5,
        restart_backoff_cap: float = 5.0,
        train_unroll: Optional[int] = None,
        group_map: Optional[Dict[int, int]] = None,
        elastic: bool = False,
        feed_segment=None,
        feed_target_bytes: Optional[int] = None) -> TPUCluster:
  """Start a cluster and run ``main_fn(tf_args, ctx)`` on every node.

  Signature parity with the reference's ``TFCluster.run``
  (TFCluster.py:215-245), with the engine abstraction in place of a
  SparkContext and TPU chip allocation in place of GPU counts.
  ``driver_ps_nodes`` hosts the ps nodes on the driver machine so every
  engine executor keeps its accelerator for workers (parity :229,298-316;
  FILES input mode only, like the reference).

  Fault tolerance: every node heartbeats the rendezvous server every
  ``heartbeat_interval`` seconds (None disables); a node silent for 2
  intervals is declared dead. With ``supervise=True`` a driver-side
  :class:`ClusterSupervisor` relaunches dead nodes (executor killed,
  preempted, OOM — NOT application exceptions, which propagate as
  always) up to ``max_restarts`` times per executor, with exponential
  backoff between ``restart_backoff`` and ``restart_backoff_cap``
  seconds. Relaunched nodes see ``ctx.restart_count > 0`` and should
  resume via ``ctx.checkpoint_manager(d).restore_or(state)``.

  ``train_unroll=K`` exports ``TOS_TRAIN_UNROLL=K`` into every node so
  ``parallel.sharding.make_train_loop`` / ``data.readers.slab_batches``
  default to fusing K optimizer steps per dispatch (1/None = the
  per-step status quo; see docs/PERFORMANCE.md §Train-loop fusion).

  ``group_map={executor_id: group_id}`` declares elastic multi-group
  training topology (``parallel.groups``): the rendezvous server grows a
  :class:`~parallel.groups.SyncPlane` (SYNC/SYNCQ/GROUP verbs + HEALTH
  ``groups`` telemetry) and supervisor events carry the group. With
  ``elastic=True`` a grouped executor that exhausts its restart budget
  COMMITS A SHRINK — surviving groups keep stepping with the sync
  denominator reduced — instead of failing the job; only losing every
  group is fatal. ``ClusterSupervisor.readmit`` re-opens the budget when
  capacity returns (docs/ROBUSTNESS.md §Elastic training).

  ``feed_segment`` (a ``data.datapipe.FeederSegment`` from
  ``Dataset.split_pushdown()``) runs the graph's pushable map/filter
  prefix inside every feeder task BEFORE the wire codec — filtered rows
  never ship, projecting maps shrink columns on the wire; the consumer
  side runs the remainder graph. ``feed_target_bytes`` sets the feeders'
  adaptive per-envelope byte budget (see ``node.ENV_FEED_TARGET_BYTES``;
  None/0 keeps the fixed ``feed_chunk_size`` row count). See
  docs/PERFORMANCE.md §Wire efficiency.
  """
  num_executors = num_executors or engine.num_executors
  if train_unroll is not None and int(train_unroll) < 1:
    raise ValueError("train_unroll must be >= 1, got %r" % (train_unroll,))
  if feed_target_bytes is not None and int(feed_target_bytes) < 0:
    raise ValueError("feed_target_bytes must be >= 0, got %r"
                     % (feed_target_bytes,))
  if feed_transport not in ("auto", "shm", "queue"):
    raise ValueError("feed_transport must be 'auto', 'shm' or 'queue', "
                     "got %r" % (feed_transport,))
  # an EXPLICIT 'shm' that a node cannot honour is an error at the driver;
  # only 'auto' may settle for the queue (node bring-up checks this flag)
  feed_transport_strict = feed_transport == "shm"
  if feed_transport == "auto":
    # shared-memory rings require the feeder task and the node to share a
    # host, which only engines with colocated executors guarantee; the
    # node itself still falls back to "queue" if the native ring is absent
    feed_transport = "shm" if getattr(engine, "colocated_executors", False) \
        else "queue"
  if driver_ps_nodes and input_mode != InputMode.FILES:
    raise ValueError("driver_ps_nodes requires InputMode.FILES/TENSORFLOW "
                     "(parity with the reference)")
  engine_nodes = num_executors - (num_ps if driver_ps_nodes else 0)
  if engine_nodes > engine.num_executors:
    raise ValueError("cluster of %d nodes needs %d executors but engine has %d"
                     % (num_executors, engine_nodes, engine.num_executors))

  # role template (parity: TFCluster.py:256-271): ps nodes first, then
  # master/chief, evaluator, workers
  num_master = 1 if master_node else 0
  num_eval = 1 if eval_node else 0
  num_workers = max(num_executors - num_ps - num_eval - num_master, 0)
  total = num_ps + num_master + num_eval + num_workers
  assert total == num_executors, \
      "cluster requires %d nodes but %d executors reserved" % (total,
                                                               num_executors)
  assert num_master + num_workers > 0, \
      "cluster requires at least one worker or master/chief node"
  if num_ps > 0:
    logger.warning(
        "num_ps=%d: parameter servers are API-compatible but architecturally "
        "obsolete on TPU — synchronous data parallelism over ICI is the "
        "native strategy; ps nodes will run as background sidecars", num_ps)

  executors = list(range(num_executors))
  cluster_template: Dict[str, List[int]] = {}
  idx = 0
  if num_ps:
    cluster_template["ps"] = executors[idx:idx + num_ps]
    idx += num_ps
  if num_master:
    cluster_template[master_node] = executors[idx:idx + 1]
    idx += 1
  if num_eval:
    cluster_template["evaluator"] = executors[idx:idx + 1]
    idx += 1
  if num_workers:
    cluster_template["worker"] = executors[idx:]
  logger.info("cluster template: %s", cluster_template)

  # startup grace = the reservation window: a node is allowed to sit
  # between REG and its first own beat for as long as cluster assembly may
  # legitimately take (executor deaths in that window are still caught by
  # the engine's ExecutorLost signal)
  server = rendezvous.Server(num_executors,
                             heartbeat_interval=heartbeat_interval,
                             startup_grace=reservation_timeout)
  if obs_metrics.enabled():
    # the driver end of the obs plane: executors ship metric/span deltas
    # through the rendezvous OBS verb into this bounded sink
    from tensorflowonspark_tpu.obs import collector as obs_collector
    from tensorflowonspark_tpu.obs import device as obs_device
    server.obs_sink = obs_collector.ObsSink()
    # compile/device tier, driver side: the driver jits too (sharded
    # init, serving warm-up) and its compiles belong on the timeline
    obs_device.install(None)
  if group_map or elastic:
    # the driver end of the elastic-training plane: groups exchange
    # weights through the SYNC verbs, HEALTH replies carry the topology
    from tensorflowonspark_tpu.parallel import groups as groups_mod
    groups_mod.attach_sync_plane(server)
  server_addr = server.start()

  cluster_meta = {
      "id": random.getrandbits(64),
      "cluster_template": cluster_template,
      "num_executors": num_executors,
      "server_addr": list(server_addr),
      "authkey": os.urandom(16),
      "queues": list(queues),
      "input_mode": input_mode,
      "default_fs": engine.default_fs(),
      "reservation_timeout": reservation_timeout,
      "tensorboard": tensorboard,
      "log_dir": log_dir,
      "release_port": release_port,
      "chips_per_node": chips_per_node,
      "qmax": qmax,
      # "queue" (manager-proxy, works everywhere) or "shm" (native
      # shared-memory ring for the input stream; single host or per-host).
      # The default "auto" resolved above: shm on colocated engines.
      "feed_transport": feed_transport,
      "feed_transport_strict": feed_transport_strict,
      # rows per feed chunk: one codec envelope / ring payload per chunk —
      # the transport batching unit AND the columnar assembly granularity
      "feed_chunk_size": feed_chunk_size,
      "shm_capacity": max(shm_capacity, 8 * 1024 * 1024),
      "heartbeat_interval": heartbeat_interval,
      # fused train loop default: every node exports this as
      # TOS_TRAIN_UNROLL (node._apply_node_env) so make_train_loop /
      # slab_batches resolve the cluster's K without per-fn plumbing
      "train_unroll": int(train_unroll) if train_unroll else None,
      # elastic multi-group training (parallel.groups): executor -> mesh
      # group id, and whether a group past its restart budget shrinks the
      # group set (resize) instead of failing the job
      "group_map": ({int(k): int(v) for k, v in group_map.items()}
                    if group_map else None),
      "elastic": bool(elastic),
      # wire-efficient feed plane (docs/PERFORMANCE.md §Wire efficiency):
      # the pushdown segment feeder tasks run before the codec, and the
      # adaptive per-envelope byte budget (None/0 = fixed row count)
      "feed_segment": feed_segment,
      "feed_target_bytes": (int(feed_target_bytes)
                            if feed_target_bytes else None),
  }

  # launch node bring-up asynchronously so that (a) feeding can start and
  # (b) reservation failures surface through tf_status (parity :318-336)
  tf_status: Dict[str, Optional[str]] = {"error": None}
  node_fn = node_mod.make_node_fn(main_fn, tf_args, cluster_meta)

  driver_ps_procs = []
  if driver_ps_nodes and num_ps:
    # ps nodes run on the driver machine in their own processes/workdirs
    import cloudpickle
    import multiprocessing as mp
    import tempfile
    mapfn_bytes = cloudpickle.dumps(node_fn)
    ctx_mp = mp.get_context("spawn")
    for ps_id in cluster_template["ps"]:
      wd = tempfile.mkdtemp(prefix="tos_driver_ps_%d_" % ps_id)
      p = ctx_mp.Process(target=node_mod.driver_node_main,
                         args=(mapfn_bytes, ps_id, wd),
                         name="driver-ps-%d" % ps_id)
      p.start()
      driver_ps_procs.append(p)
    engine_ids = [i for i in executors if i not in cluster_template["ps"]]
  else:
    engine_ids = executors

  node_job = engine.run_on_executors(node_fn, num_tasks=len(engine_ids),
                                     task_payloads=engine_ids)

  def _watch_job():
    # poll: a single failed bring-up task must surface its traceback
    # immediately (aborting await_reservations), not after the surviving
    # tasks run out their reservation timeout; driver-hosted ps processes
    # get the same treatment (a crashed child has a nonzero exitcode).
    # Executor-death errors (the ExecutorLost marker) belong to the
    # supervisor when one is running — it relaunches instead of aborting,
    # and sets tf_status itself when the restart budget runs out.
    while not node_job.done():
      err = node_job.first_error()
      if supervise and is_executor_lost(err):
        err = None
      for p in driver_ps_procs:
        if p.exitcode not in (None, 0):
          err = err or ("driver ps process %s exited with code %s during "
                        "bring-up" % (p.name, p.exitcode))
      if err:
        tf_status["error"] = err
        return
      time.sleep(0.25)
    err = node_job.first_error()
    if err and not (supervise and is_executor_lost(err)):
      tf_status["error"] = err

  threading.Thread(target=_watch_job, daemon=True,
                   name="node-job-watcher").start()

  # the supervisor starts BEFORE the reservation wait so executors dying
  # during bring-up are already relaunched (cluster_info is patched in
  # place as nodes register); only engine-hosted nodes are supervised —
  # driver_ps processes live on the driver machine outside any engine slot
  cluster_info: List[dict] = []
  supervisor = None
  if supervise:
    supervisor = ClusterSupervisor(
        engine, server, node_job, cluster_meta, cluster_info, engine_ids,
        tf_status, max_restarts=max_restarts, backoff=restart_backoff,
        backoff_cap=restart_backoff_cap).start()

  # the online consumer of the obs plane: a driver thread evaluating the
  # sink's rolling windows (stragglers, feed stalls, recompile storms,
  # serving saturation, memory slope). Alerts are counted + mirrored into
  # the supervisor event stream + JSONL'd + served over HEALTH — never
  # raised. Starts before the reservation wait so bring-up is covered.
  detector = None
  if server.obs_sink is not None:
    from tensorflowonspark_tpu.obs import anomaly as obs_anomaly
    if obs_anomaly.detect_enabled():
      # ONE driver ProcessLog, shared with the shutdown span/metrics dump
      # (TPUCluster._driver_obs_log) — two instances would write two meta
      # headers into the same obs-driver0-<pid>.jsonl
      rec = obs_spans.active()
      detector = obs_anomaly.AnomalyDetector(
          server.obs_sink, supervisor=supervisor,
          jsonl=_driver_obs_log(rec)).start()
      server.alert_source = detector

  def _abort_cleanup():
    if supervisor is not None:
      supervisor.stop()
    if detector is not None:
      detector.stop()
    server.stop()
    for p in driver_ps_procs:
      p.terminate()

  try:
    with TPUCluster._span("cluster.assemble", nodes=num_executors):
      cluster_info.extend(server.await_reservations(
          timeout=reservation_timeout, status=tf_status))
  except Exception:
    _abort_cleanup()
    raise

  # duplicate-node sanity check (parity: TFCluster.py:357-372)
  if server.reservations.duplicates:
    _abort_cleanup()
    raise RuntimeError(
        "duplicate node reservations detected (reused executors?): %r"
        % server.reservations.duplicates)

  logger.info("cluster of %d node(s) reserved: %s", len(cluster_info),
              [(n["executor_id"], n["job_name"], n["task_index"])
               for n in cluster_info])
  return TPUCluster(engine, cluster_info, cluster_meta, server, input_mode,
                    node_job, tf_status, driver_ps_procs=driver_ps_procs,
                    supervisor=supervisor, detector=detector)
