"""L2' per-executor node runtime.

Capability parity with the reference's ``TFSparkNode.py``
(/root/reference/tensorflowonspark/TFSparkNode.py), re-designed for TPU:

- device allocation exports TPU chip shares (utils.tpu_info) instead of
  ``CUDA_VISIBLE_DEVICES`` from nvidia-smi parsing (reference :179-239);
- the synthesized cluster spec feeds ``jax.distributed.initialize`` (the JAX
  analog of exporting ``TF_CONFIG``, reference :373-384) — collectives then
  compile to XLA all-reduce over ICI/DCN rather than TF gRPC;
- roles: workers run the user main fn in the foreground (FILES input mode) or
  a background process (ENGINE/SPARK input mode, reference :431-439);
  ps/evaluator run it in a background process while the foreground blocks on a
  ``control`` queue until the driver sends ``None`` (reference :441-458);
- fault propagation parity: a dedicated ``error`` queue per executor;
  background exceptions captured as tracebacks (reference :423-429), re-raised
  at shutdown with peek-and-put-back so engine task retries still observe the
  failure (reference :644-650);
- retried bring-up tasks re-register idempotently, while a live hub from a
  concurrent duplicate forces an error (reference :259-265).
"""

import logging
import os
import socket
import subprocess
import sys
import threading
import time
import traceback
from typing import Dict, List, Optional

from tensorflowonspark_tpu.control import feedhub, rendezvous
from tensorflowonspark_tpu.utils import (compile_cache, hostinfo, paths,
                                         platform_env, tpu_info)

logger = logging.getLogger(__name__)

JAX_ROLES = ("chief", "master", "worker")  # roles that join the JAX mesh
BACKGROUND_ROLES = ("ps", "evaluator")     # roles parked on a control queue

HUB_ADDR_FILE = "hub_addr"

#: pins the per-node coordinator/collectives port (env registry: TOS008)
ENV_NODE_PORT = "TOS_TPU_NODE_PORT"

#: feeder byte budget per wire envelope: when set (> 0), feeders size
#: chunks adaptively from observed encoded bytes/row instead of the fixed
#: ``feed_chunk_size`` row count — small rows stop paying per-envelope
#: manager round-trips, fat rows stop ping-ponging off ``MAX_PAYLOAD``
#: splits. ``cluster.run(feed_target_bytes=...)`` takes precedence over
#: the env. 0/unset = fixed row count. (env registry: TOS008)
ENV_FEED_TARGET_BYTES = "TOS_FEED_TARGET_BYTES"

#: adaptive-sizing row-count clamp, both directions: an envelope never
#: carries fewer rows than the floor (per-envelope overhead would
#: dominate) nor more than the cap (consumer-side latency + memory)
_ADAPT_MIN_ROWS = 16
_ADAPT_MAX_ROWS = 8192


#: env values _apply_node_env exported in THIS (persistent) executor
#: process — so a later cluster that sets nothing can retract exactly
#: what a previous cluster exported, while a user's own env pin (a value
#: we never wrote) still passes through
_applied_node_env: Dict[str, str] = {}


def _apply_node_env(meta: dict) -> None:
  """Export cluster-level training knobs into this node process's env.

  ``cluster.run(train_unroll=K)`` rides the cluster meta so EVERY node —
  foreground or spawned background runner (which inherits this env at
  spawn) — sees the same ``TOS_TRAIN_UNROLL``, which
  ``parallel.sharding.resolve_unroll`` (and thus
  ``make_train_loop``/``slab_batches``) reads as its default. An
  explicit cluster value wins over a stale env; when the cluster sets
  nothing, an export left behind by a PREVIOUS cluster on this
  persistent executor is retracted (or run B would silently fuse with
  run A's K), while a user-set env pin passes through.
  """
  from tensorflowonspark_tpu.parallel.sharding import ENV_TRAIN_UNROLL
  unroll = meta.get("train_unroll")
  if unroll:
    _applied_node_env[ENV_TRAIN_UNROLL] = str(int(unroll))
    os.environ[ENV_TRAIN_UNROLL] = _applied_node_env[ENV_TRAIN_UNROLL]
  elif _applied_node_env.get(ENV_TRAIN_UNROLL) is not None \
      and os.environ.get(ENV_TRAIN_UNROLL) == \
      _applied_node_env[ENV_TRAIN_UNROLL]:
    os.environ.pop(ENV_TRAIN_UNROLL, None)
    _applied_node_env.pop(ENV_TRAIN_UNROLL)


class TPUNodeContext(object):
  """Per-node metadata handed to the user main fn as ``ctx``.

  Field parity with the reference's TFNodeContext (TFSparkNode.py:62-108),
  plus the TPU-native coordinates (``coordinator_address``, ``process_id``,
  ``num_processes``) needed for ``jax.distributed.initialize``.
  """

  def __init__(self, executor_id=0, job_name="worker", task_index=0,
               cluster_spec=None, default_fs="file://", working_dir=".",
               hub=None, tmp_socket=None, coordinator_address=None,
               process_id=0, num_processes=1, cluster_info=None,
               restart_count=0, heartbeat=None):
    self.executor_id = executor_id
    self.worker_num = executor_id          # backwards-compat alias
    self.job_name = job_name
    self.task_index = task_index
    self.cluster_spec = cluster_spec or {}
    self.num_workers = sum(
        len(v) for k, v in self.cluster_spec.items() if k in JAX_ROLES)
    self.default_fs = default_fs
    self.defaultFS = default_fs            # backwards-compat alias
    self.working_dir = working_dir
    self.mgr = hub                         # backwards-compat alias
    self.hub = hub
    self.tmp_socket = tmp_socket
    self.coordinator_address = coordinator_address
    self.process_id = process_id
    self.num_processes = num_processes
    self.cluster_info = cluster_info or []
    #: how many times the supervisor relaunched this node (0 = first
    #: launch). A relaunched node should resume from its latest
    #: checkpoint: ``state, start = ctx.checkpoint_manager(d).restore_or(state)``
    self.restart_count = restart_count
    self._heartbeat = heartbeat

  # -- convenience mirrors (parity: TFSparkNode.py:92-108) -------------------

  def absolute_path(self, path: str) -> str:
    return paths.absolute_path(path, self.default_fs, self.working_dir)

  def get_data_feed(self, train_mode=True, qname_in="input",
                    qname_out="output", input_mapping=None,
                    liveness_timeout=600.0):
    from tensorflowonspark_tpu.datafeed import DataFeed
    return DataFeed(self.hub, train_mode, qname_in, qname_out, input_mapping,
                    liveness_timeout=liveness_timeout)

  def release_port(self) -> None:
    """Release the reserved coordinator port prior to starting JAX distributed
    (parity: TFNode.release_port, TFNode.py:214-221)."""
    if self.tmp_socket is not None:
      self.tmp_socket.close()
      self.tmp_socket = None

  def export_model(self, state, export_dir: str) -> str:
    from tensorflowonspark_tpu.utils import compat
    return compat.export_model(state, export_dir, self.is_chief)

  @property
  def is_chief(self) -> bool:
    return is_chief(self.job_name, self.task_index, self.cluster_spec)

  @property
  def is_restart(self) -> bool:
    """True when this node is a supervised relaunch of a dead predecessor."""
    return self.restart_count > 0

  def checkpoint_manager(self, directory: str, **kwargs):
    """A :class:`utils.checkpoint.CheckpointManager` for this node — the
    preemption-safe resume hook: ``state, start_step = mgr.restore_or(state)``
    continues a relaunched node from its latest checkpoint (``start_step``
    is 0 on a fresh launch)."""
    from tensorflowonspark_tpu.utils.checkpoint import CheckpointManager
    return CheckpointManager(directory, **kwargs)

  def report_progress(self, value) -> None:
    """Attach an application progress value (e.g. the training step) to
    this node's heartbeats — visible driver-side via the HEALTH verb."""
    if self._heartbeat is not None:
      self._heartbeat.set_progress(value)

  def initialize_distributed(self) -> None:
    """Join the JAX process group (TPU analog of TF reading TF_CONFIG).

    Safe to skip for single-process clusters. ps/evaluator nodes never call
    this — they are outside the mesh.
    """
    if self.num_processes <= 1:
      logger.info("single-process cluster; skipping jax.distributed")
      return
    self.release_port()
    import jax
    try:
      # CPU backends need an explicit cross-process collectives transport;
      # on TPU this knob doesn't exist and collectives ride ICI natively
      jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:  # noqa: BLE001 - unknown config name on this backend
      pass
    logger.info("joining jax process group: coordinator=%s rank=%d/%d",
                self.coordinator_address, self.process_id,
                self.num_processes)
    jax.distributed.initialize(
        coordinator_address=self.coordinator_address,
        num_processes=self.num_processes,
        process_id=self.process_id)


def is_chief(job_name: str, task_index: int, roles) -> bool:
  """Chief = the chief/master node, or worker:0 when no chief exists.

  ``roles`` is any container of job names (cluster spec or template).
  """
  return (job_name in ("chief", "master")
          or (job_name == "worker" and task_index == 0
              and not any(r in roles for r in ("chief", "master"))))


def _role_of(executor_id: int, cluster_template: Dict[str, List[int]]):
  for job_name, ids in cluster_template.items():
    if executor_id in ids:
      return job_name, ids.index(executor_id)
  raise ValueError("executor %d not present in cluster template %r"
                   % (executor_id, cluster_template))


def _jax_process_table(cluster_info: List[dict]):
  """Rank the mesh-joining nodes: chief/master first, then workers by index.

  Returns (ordered list of node metas, coordinator host:port).
  """
  chiefs = [n for n in cluster_info if n["job_name"] in ("chief", "master")]
  workers = sorted((n for n in cluster_info if n["job_name"] == "worker"),
                   key=lambda n: n["task_index"])
  table = chiefs + workers
  coord = "%s:%d" % (table[0]["host"], table[0]["port"]) if table else None
  return table, coord


def _build_cluster_spec(cluster_info: List[dict]) -> Dict[str, List[str]]:
  """{job_name: ["host:port", ...]} sorted by task index.

  Rejects duplicate executor ids (parity: TFSparkNode.py:50-53).
  """
  seen = set()
  for n in cluster_info:
    if n["executor_id"] in seen:
      raise RuntimeError("duplicate executor_id %d in cluster info"
                         % n["executor_id"])
    seen.add(n["executor_id"])
  spec: Dict[str, List[str]] = {}
  by_job: Dict[str, List[dict]] = {}
  for n in cluster_info:
    by_job.setdefault(n["job_name"], []).append(n)
  for job, nodes in by_job.items():
    spec[job] = ["%s:%d" % (n["host"], n["port"])
                 for n in sorted(nodes, key=lambda n: n["task_index"])]
  return spec


def _find_tensorboard(search_path: Optional[str] = None):
  """Locate a TensorBoard entry point, or False.

  Searches PATH, the python bin dir, sys.path and PYTHONPATH for the
  ``tensorboard`` executable, then for the module form ``tensorboard/main.py``
  (parity: the reference's three-step search, TFSparkNode.py:310-322 —
  reordered so an explicit PATH entry OVERRIDES the interpreter's bin dir,
  the conventional Unix precedence; a container may carry a stub
  ``tensorboard`` launcher next to python that shadows the real one).
  """
  if search_path is None:
    search_path = os.pathsep.join([
        os.environ.get("PATH", ""),
        os.path.dirname(sys.executable),
        os.pathsep.join(p for p in sys.path if p),
        os.environ.get("PYTHONPATH", ""),
    ])
  return hostinfo.find_in_path(search_path, "tensorboard") or \
      hostinfo.find_in_path(search_path,
                            os.path.join("tensorboard", "main.py"))


def _spawn_tensorboard(log_dir: str) -> Optional[dict]:
  """Launch a TensorBoard server subprocess (parity: TFSparkNode.py:292-329).

  Port selection: env ``TENSORBOARD_PORT`` or an ephemeral bind. Returns
  {'pid','url'} or None when no tensorboard entry point is found on the
  python bin dir / PATH / sys.path / PYTHONPATH.
  """
  tb_port = os.environ.get("TENSORBOARD_PORT")
  port = int(tb_port) if tb_port else hostinfo.get_free_port()
  tb_bin = _find_tensorboard()
  if not tb_bin:
    logger.warning("tensorboard not found on PATH/PYTHONPATH; skipping "
                   "launch")
    return None
  proc = subprocess.Popen(
      [sys.executable, tb_bin, "--logdir", log_dir, "--port", str(port),
       "--host", "0.0.0.0"],
      stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
  url = "http://%s:%d" % (hostinfo.get_ip_address(), port)
  logger.info("started TensorBoard pid=%d at %s", proc.pid, url)
  return {"pid": proc.pid, "url": url}


def _start_obs_shipper(server_addr, executor_id: int, sender):
  """Executor-side obs plane bring-up (None when ``TOS_OBS`` is off).

  The shipper shares the HeartbeatSender's clock estimator — the BEAT
  round-trip is the TIME exchange — so span timestamps anchor to the
  driver's monotonic clock without extra control-plane traffic; the
  process recorder adopts the same estimator for its JSONL exports.
  """
  from tensorflowonspark_tpu.obs import metrics as obs_metrics
  if not (obs_metrics.enabled() and server_addr):
    return None
  from tensorflowonspark_tpu.obs import collector as obs_collector
  from tensorflowonspark_tpu.obs import device as obs_device
  from tensorflowonspark_tpu.obs import spans as obs_spans
  clock = sender.clock if sender is not None else None
  rec = obs_spans.active()
  if rec is not None and clock is not None:
    rec.clock = clock
  shipper = obs_collector.ObsShipper(tuple(server_addr), executor_id,
                                     clock=clock, label="exec")
  # compile/device tier: jax.monitoring recompile sentinel + a device-
  # memory sampler on the shipper cadence, so compile counts and memory
  # watermarks ride the normal OBS wire to the driver's detector loop
  obs_device.install(shipper)
  return shipper.start()


# feeder-task obs shipper: one per executor PROCESS, shared across feed
# tasks (they are too short-lived to each own a thread + socket)
_feeder_shipper = None
_feeder_shipper_addr = None
_feeder_shipper_lock = threading.Lock()


def _ensure_feeder_shipper(server_addr, executor_id: int):
  """Obs shipper for feeder tasks (None when ``TOS_OBS`` is off).

  ENGINE-mode feed tasks run in the engine's executor process, which
  hosts no node runtime — the node's shipper
  (:func:`_start_obs_shipper`) lives in the background-runner process.
  Without a shipper HERE, the feeder-side wire counters
  (``feed.wire_bytes``/``feed.wire_rows``/``feed.wire_enc.*``) stay
  process-local and never reach the driver's sink. Cached across feed
  tasks; re-pointed when a new cluster (fresh rendezvous server) reuses
  a persistent executor process. The sink merges metric deltas
  additively per executor id, so this coexists with the node's shipper
  (the feeder process owns a disjoint metric set)."""
  global _feeder_shipper, _feeder_shipper_addr
  from tensorflowonspark_tpu.obs import metrics as obs_metrics
  if not (obs_metrics.enabled() and server_addr):
    return None
  addr = (server_addr[0], int(server_addr[1]))
  with _feeder_shipper_lock:
    if _feeder_shipper is not None and _feeder_shipper_addr == addr:
      return _feeder_shipper
    if _feeder_shipper is not None:
      _feeder_shipper.stop(timeout=1.0)
    from tensorflowonspark_tpu.obs import collector as obs_collector
    shipper = obs_collector.ObsShipper(addr, executor_id,
                                       label="feeder").start()
    _feeder_shipper = shipper
    _feeder_shipper_addr = addr
    return shipper


def _background_runner(fn_bytes: bytes, tf_args, ctx_kwargs: dict,
                       hub_addr, authkey: bytes, server_addr=None,
                       heartbeat_interval=None):
  """Entry point of the background process running the user main fn.

  Reconnects to this executor's feed hub by address (the hub lives in a
  separate manager process), captures any exception into the ``error`` queue
  as a traceback (parity: TFSparkNode.py:423-429) and drives the hub state
  machine to ``'stopped'``. Heartbeats run HERE — in the process executing
  the user fn — so a SIGKILL/OOM of this process stops the beats and the
  driver's supervisor declares the node dead.
  """
  import cloudpickle
  # the background runner is the process that jits: place JAX's
  # persistent compilation cache before the user fn's first compile
  compile_cache.setup()
  hub = feedhub.connect(tuple(hub_addr), authkey)
  sender = None
  if server_addr and heartbeat_interval:
    sender = rendezvous.HeartbeatSender(
        tuple(server_addr), ctx_kwargs["executor_id"],
        interval=heartbeat_interval).start()
  shipper = _start_obs_shipper(server_addr, ctx_kwargs["executor_id"],
                               sender)
  ctx = TPUNodeContext(hub=hub, heartbeat=sender, **ctx_kwargs)
  try:
    fn = cloudpickle.loads(fn_bytes)
    fn(tf_args, ctx)
  except BaseException:  # noqa: BLE001 - traceback must reach the driver
    tb = traceback.format_exc()
    logger.error("background main fn failed:\n%s", tb)
    try:
      hub.get_queue("error").put(tb)
    except Exception:  # noqa: BLE001 - error queue unreachable: fall back
      # so the failure still reaches the driver instead of vanishing with
      # this process (TOS004 — traceback propagation is the contract)
      try:
        hub.set("last_error", tb)   # the kv store may outlive queue breakage
      except Exception:  # noqa: BLE001 - hub manager fully gone; the
        # executor's inherited stderr is the last channel that still works
        os.write(2, ("background main fn failed:\n%s" % tb).encode())
  finally:
    if shipper is not None:
      shipper.stop()           # final delta flush + JSONL close first,
    if sender is not None:     # so the driver hears it before the bye
      sender.stop()
    try:
      hub.set("state", "stopped")
    except Exception:  # noqa: BLE001
      pass


def _refuse_if_chip_held(executor_id: int) -> None:
  """One process per chip: the background runner needs the chip, so the
  executor process spawning it must not hold one.

  Nothing in bring-up initialises a JAX backend here (the compile cache
  helper only configures; the obs device tier lives in the runner), but an
  EARLIER task on this persistent executor may have — a
  ``TFModel.transform`` or any in-process jit. The child would then fail
  or hang inside libtpu; say so instead."""
  if "jax" not in sys.modules or not platform_env.backend_initialized():
    return
  import jax
  if jax.default_backend() == "tpu":
    raise RuntimeError(
        "executor %d already initialised JAX on the TPU in this process "
        "(an earlier task on this persistent executor jitted here, e.g. "
        "TFModel.transform), so the ENGINE-mode node child it must spawn "
        "can never take the chip its parent holds. Run the cluster on "
        "fresh executors, or use InputMode.FILES (the user fn then runs "
        "in the executor process itself)" % executor_id)


def make_node_fn(main_fn, tf_args, cluster_meta: dict):
  """Build the engine task that brings up one cluster node (parity:
  TFSparkNode.run → _mapfn, TFSparkNode.py:158-465)."""
  import cloudpickle
  fn_bytes = cloudpickle.dumps(main_fn)

  def _mapfn(iterator):
    # 1. learn this task's executor id from its partition (parity :176-177).
    # A supervised relaunch hands a dict payload carrying the restart count
    # (cluster.ClusterSupervisor → Engine.relaunch_task).
    payload = next(iter(iterator))
    if isinstance(payload, dict):
      executor_id = payload["executor_id"]
      restart_count = int(payload.get("restart", 0))
    else:
      executor_id = payload
      restart_count = 0
    meta = cluster_meta
    working_dir = os.getcwd()
    job_name, task_index = _role_of(executor_id, meta["cluster_template"])
    authkey = meta["authkey"] if isinstance(meta["authkey"], bytes) \
        else bytes(meta["authkey"])

    # 2. duplicate/stale hub detection (parity :259-265): a hub in this
    # working dir that answers with our authkey and reports itself live means
    # another concurrent node task (same cluster) owns this executor — fail
    # so the engine retries elsewhere. Anything else (dead socket, stale
    # 'stopped' hub, or an AuthenticationError from a *previous* cluster's
    # hub with a different key) is reclaimed, releasing the old manager.
    reclaimed = os.path.exists(os.path.join(working_dir, HUB_ADDR_FILE))
    if reclaimed:
      old = None
      try:
        with open(os.path.join(working_dir, HUB_ADDR_FILE)) as f:
          host, port = f.read().strip().split(":")
        old = feedhub.connect((host, int(port)), authkey)
        state = old.get("state")
        if state in ("running", "terminating"):
          raise RuntimeError(
              "executor already runs a live node (hub state=%r); failing this "
              "task so the engine can retry it elsewhere" % state)
        logger.info("found stale hub (state=%r); reclaiming executor", state)
        # a SIGKILLed predecessor leaves its hub manager as a live orphan
        # (the supervisor marks it 'dead' after draining); reap it so
        # managers don't pile up across relaunches
        try:
          old.force_exit()
        except Exception:  # noqa: BLE001 - manager already gone
          pass
      except RuntimeError:
        raise
      except Exception as e:  # noqa: BLE001 - dead/foreign hub -> reclaim
        logger.info("found unreachable/foreign hub (%s); reclaiming executor",
                    type(e).__name__)
      feedhub.release(executor_id)

    # 3. start the feed hub; remote mode for driver-reachable roles
    hub_mode = "remote" if job_name in BACKGROUND_ROLES else "local"
    hub = feedhub.start(authkey, meta["queues"], mode=hub_mode,
                        qmax=meta.get("qmax", 1024))
    feedhub.hold(executor_id, hub)
    if meta.get("feed_transport") == "shm":
      # high-throughput input path: serialized chunks ride a native
      # shared-memory ring instead of manager-proxy queues; control/error/
      # output queues stay on the hub
      from tensorflowonspark_tpu.control import shmring
      if shmring.available():
        ring_name = "/tos_feed_%x_%d" % (meta["id"] & 0xFFFFFFFF,
                                         executor_id)
        if restart_count:
          # generation-suffix the relaunched node's ring: co-host feeder
          # processes cache opened rings by name (shmring.open_cached), so
          # reusing the dead predecessor's name would hand them a stale
          # mapping of an unlinked segment. Reap the old generations'
          # segments while we're here.
          shmring.unlink_stale(ring_name)
          for gen in range(1, restart_count):
            shmring.unlink_stale("%s_r%d" % (ring_name, gen))
          ring_name = "%s_r%d" % (ring_name, restart_count)
        ring = shmring.ShmRing.create(ring_name,
                                      meta.get("shm_capacity",
                                               64 * 1024 * 1024))
        shmring.hold(executor_id, ring)
        hub.set("ring_name", ring_name)
      elif meta.get("feed_transport_strict"):
        # the caller ASKED for shm: a silent queue fallback would hand
        # them a different transport than the one they are measuring
        raise RuntimeError(
            "feed_transport='shm' was requested but the native ring "
            "(native/shmring.cpp, built with g++ on first use) is "
            "unavailable on executor %d; use feed_transport='auto' to "
            "let the node choose" % executor_id)
      else:
        logger.warning("feed_transport 'auto' resolved to shm but the "
                       "native ring is unavailable; using the queue "
                       "transport")
    hostinfo.write_executor_id(executor_id, working_dir)
    with open(os.path.join(working_dir, HUB_ADDR_FILE), "w") as f:
      f.write("%s:%d" % hub.addr)

    # 5. reserve a port for the JAX coordinator / collectives endpoint
    # (parity with TF GRPC port reservation, :344-352); env pin supported
    tmp_sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    tmp_sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    tmp_sock.bind(("", int(os.environ.get(ENV_NODE_PORT, "0"))))
    port = tmp_sock.getsockname()[1]

    # Steps 6-8 run with the reserved port open in a PERSISTENT executor
    # process: a bring-up failure (TB spawn error, reservation timeout,
    # chip-allocation error) must release the socket or every supervised
    # retry leaks one fd into the executor (TOS006).
    try:
      # 6. TensorBoard on chief / worker:0 (parity :292-329)
      tb_info = None
      if meta.get("tensorboard") and is_chief(job_name, task_index,
                                              meta["cluster_template"]):
        log_dir = meta.get("log_dir") or os.path.join(working_dir,
                                                      "tensorboard")
        os.makedirs(paths.strip_scheme(log_dir), exist_ok=True)
        tb_info = _spawn_tensorboard(paths.strip_scheme(log_dir))
        if tb_info:
          hub.set("tb_pid", tb_info["pid"])
          hub.set("tb_url", tb_info["url"])

      # 7. register and wait for the whole cluster (parity :332-370)
      host = hostinfo.get_ip_address()
      client = rendezvous.Client(tuple(meta["server_addr"]))
      reservation = {
          "executor_id": executor_id,
          "host": host,
          "job_name": job_name,
          "task_index": task_index,
          "port": port,
          "hub_addr": list(hub.addr),
          "pid": os.getpid(),
          "tb_url": tb_info["url"] if tb_info else None,
          # a reclaimed stale hub proves this is a retry of a dead
          # predecessor, not a concurrent task — the rendezvous replaces
          # instead of flagging a duplicate (Reservations.add)
          "reclaimed": reclaimed,
          # restart generation: lets the supervisor recognize THIS
          # relaunch's registration (the pid alone is ambiguous — an
          # ENGINE-mode relaunch reuses the executor process)
          "restart": restart_count,
      }
      try:
        client.register(reservation)
        cluster_info = client.await_reservations(
            timeout=meta.get("reservation_timeout", 600))
      finally:
        # a reservation timeout is the COMMON bring-up failure; without
        # this the persistent executor leaks one connected client fd per
        # supervised retry (TOS006)
        client.close()

      # 7.5 TPU chip allocation (replaces nvidia-smi GPU allocation,
      # parity :179-239). Runs AFTER reservation so the host-local worker
      # index comes from the actual host population in cluster_info (parity
      # with the reference's cluster-spec-derived local index, :386-388) —
      # executor ids are NOT contiguous per host, so id % workers_per_host
      # would double-claim chips.
      # A request that cannot be honoured (no topology visible, more
      # co-hosted nodes than chips) raises and reaches the driver.
      num_chips = meta.get("chips_per_node", 0)
      if num_chips:
        cohosted = sorted(n["executor_id"] for n in cluster_info
                          if n["host"] == host)
        tpu_info.claim_chips(num_chips, cohosted.index(executor_id),
                             workers_on_host=len(cohosted),
                             what="executor %d" % executor_id)

      # 8. synthesize the cluster spec + JAX process coordinates (the TPU
      # analog of exporting TF_CONFIG, parity :373-384)
      cluster_spec = _build_cluster_spec(cluster_info)
      table, coordinator = _jax_process_table(cluster_info)
      process_id = next((i for i, n in enumerate(table)
                         if n["executor_id"] == executor_id), -1)
    except BaseException:
      tmp_sock.close()
      raise

    ctx_kwargs = dict(
        executor_id=executor_id, job_name=job_name, task_index=task_index,
        cluster_spec=cluster_spec, default_fs=meta.get("default_fs", "file://"),
        working_dir=working_dir, coordinator_address=coordinator,
        process_id=process_id, num_processes=len(table),
        cluster_info=cluster_info, restart_count=restart_count)
    hb_interval = meta.get("heartbeat_interval")

    # 9. release-port semantics (parity :400-405): by default the reserved
    # port is released before the user fn; with release_port=False user code
    # calls ctx.release_port() itself right before jax.distributed.initialize
    release_now = meta.get("release_port", True)

    # 10. run the user main fn per role (parity :417-463)
    if isinstance(tf_args, list):
      sys.argv = [sys.argv[0] if sys.argv else "main"] + list(tf_args)
    # cluster-level training knobs (train_unroll → TOS_TRAIN_UNROLL)
    # export here so BOTH the foreground fn and the spawned background
    # runner (which inherits this env) resolve the same defaults
    _apply_node_env(meta)

    if job_name in BACKGROUND_ROLES or meta["input_mode"] == 1:
      # background execution; foreground either returns (workers, so feeding
      # tasks can be scheduled onto this executor) or parks on the control
      # queue (ps/evaluator) until the driver sends None (parity :431-458)
      tmp_sock.close()
      _refuse_if_chip_held(executor_id)
      import multiprocessing as mp
      proc = mp.get_context("spawn").Process(
          target=_background_runner,
          args=(fn_bytes, tf_args, ctx_kwargs, list(hub.addr), authkey,
                list(meta["server_addr"]), hb_interval),
          daemon=True, name="tos-node-%d" % executor_id)
      proc.start()
      hub.set("node_pid", proc.pid)
      if job_name in BACKGROUND_ROLES:
        control = hub.get_queue("control")
        while True:
          items = control.get_many(1, timeout=1.0)
          if items and items[0] is None:
            break
        # flip the state off "running" FIRST — sidecar fns (e.g. the eval
        # sidecar) poll it as their stop signal — then join the background
        # process (bounded) so its work is durably done before 'stopped'
        # is reported: the driver's stop used to race a fn still starting
        hub.set("state", "terminating")
        proc.join(timeout=60)
        if proc.is_alive():
          logger.warning("%s:%d background process still running at stop; "
                         "terminating", job_name, task_index)
          proc.terminate()
        hub.set("state", "stopped")
      return [executor_id]
    else:
      # foreground execution (FILES mode workers, parity :459-463); beats
      # come from THIS process — the one the user fn runs in — so a
      # kill/hang of the worker is what stops them
      if release_now:
        tmp_sock.close()
        tmp_sock = None
      # foreground workers jit in THIS process: place the persistent
      # compilation cache before the user fn compiles — and before the
      # beats start, so the jax import it costs falls under the startup
      # grace instead of between two heartbeats
      compile_cache.setup()
      sender = None
      if hb_interval:
        sender = rendezvous.HeartbeatSender(
            tuple(meta["server_addr"]), executor_id,
            interval=hb_interval).start()
      shipper = _start_obs_shipper(meta["server_addr"], executor_id, sender)
      ctx = TPUNodeContext(hub=hub, tmp_socket=tmp_sock, heartbeat=sender,
                           **ctx_kwargs)
      try:
        cloudpickle.loads(fn_bytes)(tf_args, ctx)
        hub.set("state", "stopped")
      except BaseException:
        tb = traceback.format_exc()
        try:
          hub.get_queue("error").put(tb)
          hub.set("state", "stopped")
        except Exception:  # noqa: BLE001
          pass
        raise
      finally:
        if shipper is not None:
          shipper.stop()
        if sender is not None:
          sender.stop()
      return [executor_id]

  return _mapfn


def driver_node_main(mapfn_bytes: bytes, executor_id: int,
                     workdir: str) -> None:
  """Entry point for a node hosted on the DRIVER machine (driver_ps_nodes,
  parity: reference TFCluster.py:298-316): runs the same bring-up mapfn a
  regular executor would, in its own working directory."""
  import cloudpickle
  os.makedirs(workdir, exist_ok=True)
  os.chdir(workdir)
  mapfn = cloudpickle.loads(mapfn_bytes)
  mapfn(iter([executor_id]))


# --- data-plane task factories (parity: TFSparkNode.train/inference) --------


def _get_hub(cluster_info: List[dict], executor_id: int, authkey: bytes):
  """Locate the feed hub of the node that owns this executor working dir
  (parity: TFSparkNode._get_manager, TFSparkNode.py:128-155).

  The working dir's ``hub_addr`` file is authoritative: a supervised
  relaunch starts a FRESH hub and rewrites the file, while ``cluster_info``
  pickled into an already-submitted feed task still names the dead one.
  Falls back to cluster_info when the file is missing/unreadable.
  """
  hub_file = os.path.join(os.getcwd(), HUB_ADDR_FILE)
  try:
    with open(hub_file) as f:
      host, port = f.read().strip().split(":")
    return feedhub.connect((host, int(port)), authkey)
  except Exception:  # noqa: BLE001 - fall back to the reservation table
    pass
  for n in cluster_info:
    if n["executor_id"] == executor_id:
      return feedhub.connect(tuple(n["hub_addr"]), authkey)
  raise RuntimeError("no cluster node found for executor %d" % executor_id)


def _open_advertised_ring(hub, qname: str):
  """The node's shm ring adapter, or None (not advertised / unreachable).

  One shared resolution for the producer and consumer paths so their
  fallback behavior cannot drift."""
  if qname != "input":
    return None
  ring_name = hub.get("ring_name")
  if not ring_name:
    return None
  from tensorflowonspark_tpu.control import shmring
  try:
    return shmring.RingQueueAdapter(shmring.open_cached(ring_name))
  except Exception as e:  # noqa: BLE001 - cross-host/absent/released ring
    logger.warning("advertised shm ring %r unreachable from this process "
                   "(%s); using the hub queue", ring_name, type(e).__name__)
    return None


def input_channel(hub, qname: str = "input"):
  """PRODUCER-side input stream: the shared-memory ring when the node
  advertises one (feed_transport='shm') and it is reachable from this
  process, else the hub queue. Both expose the same put/get/join surface
  (control.shmring.RingQueueAdapter).

  A feeder task scheduled onto a DIFFERENT host (multi-host Spark) cannot
  open the node's ring — it falls back to the hub queue, and the node's
  consumer drains both (:class:`DualInput`)."""
  ring = _open_advertised_ring(hub, qname)
  return ring if ring is not None else hub.get_queue(qname)


def _slice_chunk(chunk, a: int, b: int):
  """Row-range slice of a pending chunk (row list or ColumnChunk)."""
  from tensorflowonspark_tpu.control import chunkcodec
  if isinstance(chunk, chunkcodec.ColumnChunk):
    return chunkcodec.ColumnChunk([c[a:b] for c in chunk.cols],
                                  chunk.scalar, chunk.tuples, b - a)
  return chunk[a:b]


def put_rows_chunk(channel, rows, timeout=None, stats=None) -> int:
  """Ship one feed chunk as one or more chunk-boundary envelopes.

  The chunk is encoded ONCE in the feeder process (columnar for
  homogeneous rows, with per-column wire encodings —
  ``control/chunkcodec.py``) and travels as one unit on either transport:
  a ring payload, or a hub-queue ``ChunkEnvelope`` whose manager pickle
  is a bytes memcpy instead of a per-row object walk. Chunk boundaries
  survive to the consumer, which is what lets ``DataFeed`` assemble
  batches from column views instead of row tuples.

  Splitting operates on the ENCODED payload size (compression widens the
  effective row budget): oversized chunks halve at the row level until
  every envelope fits ``chunkcodec.MAX_PAYLOAD``, in row order. A SINGLE
  row whose encoded payload still exceeds the bound raises
  :class:`chunkcodec.OversizedRowError` — a structured error instead of
  the former unbounded recursion.

  ``rows`` may be a row list or an already-columnar ``ColumnChunk``
  (e.g. a pushdown segment's output). Returns total encoded bytes
  shipped; ``stats`` (optional dict) accumulates per-column encoding
  counts for chunks that shipped.
  """
  from tensorflowonspark_tpu.control import chunkcodec
  from tensorflowonspark_tpu.obs import metrics as obs_metrics
  if not isinstance(rows, chunkcodec.ColumnChunk):
    rows = list(rows)
  enc_counts: Dict[str, int] = {}
  total_bytes = 0
  total_rows = 0
  # LIFO work stack: push the back half first so rows ship in order
  stack = [rows]
  while stack:
    chunk = stack.pop()
    n = chunk.n if isinstance(chunk, chunkcodec.ColumnChunk) else len(chunk)
    tally: Dict[str, int] = {}
    payload = chunkcodec.encode(chunk, tally)
    if len(payload) > chunkcodec.MAX_PAYLOAD:
      if n <= 1:
        raise chunkcodec.OversizedRowError(
            "a single row encodes to %d bytes, above the transport bound "
            "(chunkcodec.MAX_PAYLOAD = %d); it cannot be split further at "
            "the row level" % (len(payload), chunkcodec.MAX_PAYLOAD))
      half = n // 2
      stack.append(_slice_chunk(chunk, half, n))
      stack.append(_slice_chunk(chunk, 0, half))
      continue
    channel.put_chunk(n, payload, block=True, timeout=timeout)
    total_bytes += len(payload)
    total_rows += n
    # merge the tally only for envelopes that actually shipped (an
    # oversized encode attempt is re-encoded after the split)
    for name, cnt in tally.items():
      enc_counts[name] = enc_counts.get(name, 0) + cnt
  if stats is not None:
    for name, cnt in enc_counts.items():
      stats[name] = stats.get(name, 0) + cnt
  reg = obs_metrics.active()
  if reg is not None and total_rows:
    reg.counter("feed.wire_bytes").inc(total_bytes)
    reg.counter("feed.wire_rows").inc(total_rows)
    for name, cnt in enc_counts.items():
      reg.counter("feed.wire_enc." + name).inc(cnt)
  return total_bytes


class _ChunkSizer(object):
  """Adaptive rows-per-envelope targeting ``target`` encoded bytes.

  Tracks an EWMA of observed encoded bytes per SOURCE row (pushdown and
  compression both fold into the ratio: a selective filter or a 4x codec
  simply makes source rows cheap on the wire, so the next envelope
  carries more of them). The row target stays clamped to
  ``[_ADAPT_MIN_ROWS, _ADAPT_MAX_ROWS]`` both ways."""

  __slots__ = ("target", "rows", "_bpr")

  def __init__(self, base_rows: int, target_bytes: int):
    self.target = int(target_bytes)
    self.rows = max(_ADAPT_MIN_ROWS, min(int(base_rows), _ADAPT_MAX_ROWS))
    self._bpr = 0.0

  def observe(self, n_rows: int, n_bytes: int) -> None:
    if n_rows <= 0:
      return
    bpr = n_bytes / float(n_rows)
    self._bpr = bpr if not self._bpr else 0.5 * self._bpr + 0.5 * bpr
    if self._bpr > 0:
      self.rows = max(_ADAPT_MIN_ROWS,
                      min(int(self.target / self._bpr), _ADAPT_MAX_ROWS))


def _feed_plan(cluster_meta: Dict, chunk_size: Optional[int]):
  """Resolve one feeder task's shipping plan from cluster_meta (executor
  side): ``(chunk_size, run_segment, sizer)``. The pushdown segment
  compiles once per task; the sizer exists only when a byte budget is
  set (``feed_target_bytes`` cluster param, else ``TOS_FEED_TARGET_BYTES``)."""
  from tensorflowonspark_tpu.control import chunkcodec
  chunk_size = chunk_size or cluster_meta.get("feed_chunk_size", 256)
  # a new stream's columns owe nothing to the last one: drop any probe
  # backoff left by a previous feeder task in this process, or a fresh
  # compressible stream would ship its leading chunks raw
  chunkcodec._probe_backoff.clear()
  segment = cluster_meta.get("feed_segment")
  run_segment = segment.compile() if segment is not None else None
  target = cluster_meta.get("feed_target_bytes")
  if not target:
    try:
      target = int(os.environ.get(ENV_FEED_TARGET_BYTES, "0") or 0)
    except ValueError:
      target = 0
  sizer = _ChunkSizer(chunk_size, target) if target and target > 0 else None
  return chunk_size, run_segment, sizer


def _flush_chunk(queue, chunk, run_segment, sizer, timeout,
                 stats=None) -> int:
  """Apply the pushdown segment (if any) to one accumulated source chunk
  and ship the survivors. Returns rows actually DELIVERED (post-segment)
  — a pushed-down filter drops rows feeder-side, and inference collects
  one result per delivered row, not per source row. The sizer observes
  SOURCE rows against shipped bytes so its budget covers the whole
  segment+codec pipeline."""
  src_n = len(chunk)
  out = chunk
  if run_segment is not None:
    out = run_segment(chunk)
  n = 0 if out is None else (out.n if hasattr(out, "n") else len(out))
  nbytes = put_rows_chunk(queue, out, timeout=timeout, stats=stats) \
      if n else 0
  if sizer is not None and src_n:
    sizer.observe(src_n, nbytes)
  return n


class DualInput(object):
  """CONSUMER-side input draining the shm ring AND the hub queue.

  Co-host feeders (and the end-of-feed markers from co-hosted shutdown
  tasks) arrive on the ring; feeders on other hosts — and shutdown tasks
  the shared queue placed off-host — fall back to the hub queue.
  Per-partition row order is
  preserved because any single feeder uses exactly one channel.
  ``task_done`` routes to whichever channel produced the last batch, so
  queue join backpressure still works for remote feeders.

  An end-of-feed ``None`` arriving on the ring (shutdown marker, or the
  adapter's synthesized marker when the ring closes) is HELD BACK while
  the hub queue still has rows — a marker must never overtake remote
  feeders' in-flight data.
  """

  def __init__(self, ring, queue):
    self._ring = ring
    self._queue = queue
    self._last = None
    self._stash = None    # ring tail (from the marker on) awaiting drain
    self._stash_chunk = None  # held-back end-of-feed chunk (get_chunk path)
    #: deliveries per channel — which transport actually ran
    self.deliveries = {"ring": 0, "queue": 0}

  def _from(self, ch, got):
    self._last = ch
    self.deliveries["ring" if ch is self._ring else "queue"] += 1
    return got

  def _deliver_ring(self, got, max_items: int):
    # identity scan, not `None in got`: rows may be numpy arrays, whose
    # __eq__ is elementwise and makes `in`/.index raise on truth-testing
    idx = next((i for i, r in enumerate(got) if r is None), -1)
    if idx >= 0 and not self._queue.empty():
      self._stash = got[idx:]
      prefix = got[:idx]
      if prefix:
        return self._from(self._ring, prefix)
      queued = self._queue.get_many(max_items, block=False)
      if queued:
        return self._from(self._queue, queued)
      # the queue drained between the check and the read: release now
      out, self._stash = self._stash, None
      return self._from(self._ring, out)
    return self._from(self._ring, got)

  def get_many(self, max_items: int, block: bool = True, timeout=None):
    import time as _time
    if self._stash is not None:
      queued = self._queue.get_many(max_items, block=False)
      if queued:
        return self._from(self._queue, queued)
      out, self._stash = self._stash, None
      return self._from(self._ring, out)
    # same blocking contract as the single-channel queues: timeout=None
    # blocks until data arrives (alternating short polls of both channels)
    deadline = None if timeout is None else _time.monotonic() + timeout
    while True:
      got = self._ring.get_many(max_items, block=False)
      if got:
        return self._deliver_ring(got, max_items)
      got = self._queue.get_many(max_items, block=False)
      if got:
        return self._from(self._queue, got)
      if not block:
        return []
      remaining = None if deadline is None else deadline - _time.monotonic()
      if remaining is not None and remaining <= 0:
        return []
      wait = 0.25 if remaining is None else min(remaining, 0.25)
      got = self._ring.get_many(max_items, block=True, timeout=wait)
      if got:
        return self._deliver_ring(got, max_items)

  def _ring_chunk(self, got, max_rows: int):
    """Deliver a ring chunk, holding back an end-of-feed marker while the
    hub queue still has remote feeders' data (get_chunk analog of
    ``_deliver_ring``)."""
    if got[0] == "marker" and got[1] is None and not self._queue.empty():
      queued = self._queue.get_chunk(max_rows, block=False)
      if queued:
        self._stash_chunk = got
        return self._from(self._queue, queued)
      # the queue drained between the check and the read: release now
    return self._from(self._ring, got)

  def get_chunk(self, max_rows: int = 1024, block: bool = True,
                timeout=None):
    """Chunk-granular dequeue over both channels (``None`` on timeout).

    Same contract as the single-channel ``get_chunk``: one chunk-boundary
    unit per call; an end-of-feed ``None`` chunk from the ring waits for
    the hub queue to drain, exactly like the row-granular path."""
    import time as _time
    if self._stash_chunk is not None:
      queued = self._queue.get_chunk(max_rows, block=False)
      if queued:
        return self._from(self._queue, queued)
      out, self._stash_chunk = self._stash_chunk, None
      return self._from(self._ring, out)
    deadline = None if timeout is None else _time.monotonic() + timeout
    while True:
      got = self._ring.get_chunk(max_rows, block=False)
      if got:
        return self._ring_chunk(got, max_rows)
      got = self._queue.get_chunk(max_rows, block=False)
      if got:
        return self._from(self._queue, got)
      if not block:
        return None
      remaining = None if deadline is None else deadline - _time.monotonic()
      if remaining is not None and remaining <= 0:
        return None
      wait = 0.25 if remaining is None else min(remaining, 0.25)
      got = self._ring.get_chunk(max_rows, block=True, timeout=wait)
      if got:
        return self._ring_chunk(got, max_rows)

  def task_done(self, n: int = 1) -> None:
    if self._last is not None:
      self._last.task_done(n)

  def qsize(self) -> int:
    return self._ring.qsize() + self._queue.qsize()

  def empty(self) -> bool:
    return self.qsize() == 0


def consumer_channel(hub, qname: str = "input"):
  """The node-side input stream: ring+queue dual when a ring is
  advertised and reachable (see :class:`DualInput`), else the hub queue."""
  ring = _open_advertised_ring(hub, qname)
  if ring is not None:
    return DualInput(ring, hub.get_queue(qname))
  return hub.get_queue(qname)


def _check_errors(hub, where: str) -> None:
  """Poll the error queue; re-raise worker tracebacks on the feeder/driver
  side (parity: TFSparkNode.py:508-515)."""
  eq = hub.get_queue("error")
  errs = eq.get_many(16, block=False)
  if errs:
    # put back so shutdown's check still sees it (parity :644-650)
    eq.put_many(errs)
    raise RuntimeError("worker error detected during %s:\n%s"
                       % (where, "\n".join(str(e) for e in errs)))


_NO_ITEM = object()


def _materialize_partition(iterator):
  """Resolve a lazy partition handle on the executor.

  A partition consisting of exactly ONE zero-arg callable (e.g. from
  ``data.dfutil.load_tfrecords(lazy=True)``) is a handle: call it HERE so
  rows are produced executor-side and never ship through the driver (the
  feed-plane counterpart of save_as_tfrecords' callable partitions;
  parity: reference loadTFRecords parsing records on executors,
  dfutil.py:44-81). Anything else passes through untouched.
  """
  import itertools
  first = next(iterator, _NO_ITEM)
  if first is _NO_ITEM:
    return iter(())
  if callable(first):
    second = next(iterator, _NO_ITEM)
    if second is _NO_ITEM:
      return iter(first())
    return itertools.chain([first, second], iterator)
  return itertools.chain([first], iterator)


def make_train_fn(cluster_info, cluster_meta, feed_timeout=600, qname="input",
                  chunk_size=None):
  """Feeder task: push one data partition into the local node's input queue.

  TPU-first redesign of the reference's row-at-a-time loop
  (TFSparkNode.py:500-502): rows move as chunk-boundary envelopes via
  ``put_rows_chunk`` — encoded once (columnar for homogeneous rows) and
  shipped whole — preserving blocking backpressure and the
  terminating-state drain semantics (TFSparkNode.py:492-531).
  ``chunk_size`` defaults to the cluster's ``feed_chunk_size``; a
  ``feed_segment`` in cluster_meta (datapipe pushdown) runs here before
  the codec, and a ``feed_target_bytes`` budget sizes chunks adaptively.
  """
  authkey = cluster_meta["authkey"]

  def _train(iterator):
    executor_id = hostinfo.read_executor_id(os.getcwd())
    from tensorflowonspark_tpu.utils import chaos
    chaos.stall_point("feeder", index=executor_id)
    hub = _get_hub(cluster_info, executor_id, authkey)
    state = hub.get("state")
    queue = input_channel(hub, qname)
    if state == "terminating":
      # user called DataFeed.terminate(): consume and discard the partition
      # so the engine job completes (parity :492-496). The RAW iterator is
      # drained — a lazy handle is discarded uncalled, never decoded
      logger.info("node terminating; skipping partition feed")
      for _ in iterator:
        pass
      return [0]
    shipper = _ensure_feeder_shipper(cluster_meta.get("server_addr"),
                                     executor_id)
    size, run_segment, sizer = _feed_plan(cluster_meta, chunk_size)
    iterator = _materialize_partition(iterator)
    rows = 0
    flushes = 0
    chunk = []
    for item in iterator:
      chunk.append(item)
      if len(chunk) >= (sizer.rows if sizer is not None else size):
        rows += len(chunk)
        _flush_chunk(queue, chunk, run_segment, sizer, feed_timeout)
        chunk = []
        flushes += 1
        # poll the error queue every 8th flushed chunk — at the flush
        # point only (a per-item check would re-fire hundreds of times
        # while the count sits on a boundary value)
        if flushes % 8 == 0:
          _check_errors(hub, "feeding")
    if chunk:
      rows += len(chunk)
      _flush_chunk(queue, chunk, run_segment, sizer, feed_timeout)
    # wait until the consumer processed everything, surfacing errors
    # (parity :504-517)
    deadline = time.monotonic() + feed_timeout
    while not queue.join(timeout=1.0):
      _check_errors(hub, "feeding")
      if time.monotonic() > deadline:
        raise TimeoutError(
            "feed timeout (%ds) waiting for node to consume %d rows"
            % (feed_timeout, rows))
    _check_errors(hub, "feeding")
    if shipper is not None:
      # final flush: this may be the run's last feed task, and engine
      # teardown won't wait for the cadence thread's next round
      shipper.ship(timeout=5.0)
    logger.info("fed %d rows to executor %d", rows, executor_id)
    return [rows]

  return _train


def make_inference_fn(cluster_info, cluster_meta, feed_timeout=600,
                      qname="input", chunk_size=None):
  """Inference task: feed one partition, collect its results from the output
  queue (parity: TFSparkNode.inference, TFSparkNode.py:538-599)."""
  authkey = cluster_meta["authkey"]

  def _inference(iterator):
    from tensorflowonspark_tpu.control.marker import EndPartition
    iterator = _materialize_partition(iterator)
    executor_id = hostinfo.read_executor_id(os.getcwd())
    hub = _get_hub(cluster_info, executor_id, authkey)
    queue = input_channel(hub, qname)
    shipper = _ensure_feeder_shipper(cluster_meta.get("server_addr"),
                                     executor_id)
    size, run_segment, sizer = _feed_plan(cluster_meta, chunk_size)
    # `count` is rows DELIVERED to the node (post-pushdown): a pushed-down
    # filter drops rows feeder-side and they produce no results, so the
    # collection loop below must not wait for them
    count = 0
    chunk = []
    for item in iterator:
      chunk.append(item)
      if len(chunk) >= (sizer.rows if sizer is not None else size):
        count += _flush_chunk(queue, chunk, run_segment, sizer, feed_timeout)
        chunk = []
    if chunk:
      count += _flush_chunk(queue, chunk, run_segment, sizer, feed_timeout)
    if count == 0:
      return []  # empty/fully-filtered partitions short-circuit (parity :569-570)
    queue.put(EndPartition(), block=True, timeout=feed_timeout)

    deadline = time.monotonic() + feed_timeout
    while not queue.join(timeout=1.0):
      _check_errors(hub, "inference feeding")
      if time.monotonic() > deadline:
        raise TimeoutError("feed timeout (%ds) during inference" % feed_timeout)

    # collect exactly `count` results (parity :588-595)
    out_q = hub.get_queue("output")
    results = []
    while len(results) < count:
      got = out_q.get_many(count - len(results), timeout=feed_timeout)
      if not got:
        _check_errors(hub, "inference collection")
        if time.monotonic() > deadline:
          raise TimeoutError("timed out collecting inference results")
        continue
      results.extend(got)
      out_q.task_done(len(got))
    if shipper is not None:
      shipper.ship(timeout=5.0)   # final flush before the task returns
    return results

  return _inference


def _kill_tensorboard(hub) -> None:
  """SIGTERM this node's TensorBoard if it started one (parity :619-625)."""
  tb_pid = hub.get("tb_pid")
  if tb_pid:
    try:
      os.kill(int(tb_pid), 15)
    except OSError:
      pass


def make_tb_kill_fn(cluster_info, cluster_meta):
  """Engine task killing a node's TensorBoard (FILES-mode shutdown — there
  is no feed-shutdown job to fold it into, unlike ENGINE mode).

  Best-effort by design: a dead node/hub must not abort the rest of
  shutdown (server stop, sidecar stops, error propagation)."""
  authkey = cluster_meta["authkey"]

  def _kill(iterator):
    for _ in iterator:
      pass
    try:
      executor_id = hostinfo.read_executor_id(os.getcwd())
      _kill_tensorboard(_get_hub(cluster_info, executor_id, authkey))
    except Exception as e:  # noqa: BLE001 - reap is best-effort
      logger.warning("tensorboard reap skipped on this executor: %s", e)

  return _kill


def make_shutdown_fn(cluster_info, cluster_meta, grace_secs=0,
                     queues=("input",)):
  """Shutdown task: send end-of-feed, await node exit, surface late errors
  (parity: TFSparkNode.shutdown, TFSparkNode.py:602-656).

  The partition payload names the executor whose node this task stops.
  Engine shutdown tasks ride the SHARED queue, so both tasks can land on
  whichever executor frees up first — if this task acted on the slot it
  happens to occupy, one node could receive two end-of-feed markers while
  the other receives none and hangs until engine teardown. Host-local side
  effects (TensorBoard SIGTERM, /dev/shm reap) only run when the target
  node is co-hosted with this task."""
  authkey = cluster_meta["authkey"]

  def _host_of(eid):
    for n in cluster_info:
      if n["executor_id"] == eid:
        return n["hub_addr"][0]
    return None

  def _shutdown(iterator):
    target = None
    for item in iterator:
      target = item
    here = hostinfo.read_executor_id(os.getcwd())
    executor_id = here if target is None else int(target)
    if executor_id == here:
      # local: the cwd hub_addr file is authoritative (relaunched nodes
      # rewrite it; cluster_info may still name the dead hub)
      hub = _get_hub(cluster_info, executor_id, authkey)
    else:
      entry = next((n for n in cluster_info
                    if n["executor_id"] == executor_id), None)
      if entry is None:
        raise RuntimeError("no cluster node found for executor %d"
                           % executor_id)
      hub = feedhub.connect(tuple(entry["hub_addr"]), authkey)
    co_hosted = executor_id == here or _host_of(executor_id) == _host_of(here)

    if co_hosted:
      _kill_tensorboard(hub)  # pid signal — only valid on the node's host

    for qname in queues:
      input_channel(hub, qname).put(None, block=True, timeout=60)

    # wait for the node process to finish (state -> stopped)
    deadline = time.monotonic() + max(grace_secs, 0) + 600
    while hub.get("state") not in ("stopped",):
      if time.monotonic() > deadline:
        raise TimeoutError("node on executor %d did not stop" % executor_id)
      time.sleep(0.5)
    if grace_secs:
      time.sleep(grace_secs)

    # the input ring (if any) has served its purpose; unlink the shm
    # segment so repeated runs don't accumulate /dev/shm usage
    ring_name = hub.get("ring_name")
    if ring_name:
      from tensorflowonspark_tpu.control import shmring
      if executor_id == here:
        shmring.release(executor_id)
      elif co_hosted:
        # the ring is held by the target's executor process, not this one;
        # reap the segment by name (open mappings stay valid)
        shmring.unlink_stale(ring_name)

    # late-error propagation with peek-and-put-back (parity :644-650)
    eq = hub.get_queue("error")
    errs = eq.get_many(16, block=False)
    if errs:
      eq.put_many(errs)
      raise RuntimeError("worker error:\n%s" % "\n".join(str(e) for e in errs))
    # the background runner's fallback channel: a traceback it could not
    # enqueue (error queue unreachable at crash time) lands in the kv store
    last_error = hub.get("last_error")
    if last_error:
      raise RuntimeError("worker error (recovered from the hub kv store — "
                         "the error queue was unreachable when the node "
                         "crashed):\n%s" % last_error)
    return [executor_id]

  return _shutdown
