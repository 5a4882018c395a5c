"""L2'+L3' integration tests: full cluster lifecycle on the LocalEngine.

Port of the reference's distributed-integration tier
(reference tests/test_TFCluster.py, run on a 2-worker Spark standalone
cluster): independent single-node computations (:16-27), ENGINE-mode
inference round-trip sum(x^2) (:29-48), exception during feeding (:50-68),
late exception after feeding with grace_secs (:70-91), port
release/unrelease semantics (:93-121); plus ps/evaluator lifecycle.
"""

import os

import pytest

from tensorflowonspark_tpu import cluster as tos_cluster
from tensorflowonspark_tpu.cluster import InputMode
from tensorflowonspark_tpu.engine import LocalEngine


@pytest.fixture()
def engine():
  e = LocalEngine(num_executors=2)
  yield e
  e.stop()


# fake tensorboard entry point: records start/kill so tests can observe
# the node runtime's spawn and kill-on-shutdown behavior
_FAKE_TB = """\
import argparse, os, signal, sys, time
p = argparse.ArgumentParser()
p.add_argument("--logdir"); p.add_argument("--port"); p.add_argument("--host")
a, _ = p.parse_known_args()


def _bye(sig, frame):
  with open(os.path.join(a.logdir, "tb_killed.txt"), "w") as f:
    f.write("killed")
  sys.exit(0)


signal.signal(signal.SIGTERM, _bye)
with open(os.path.join(a.logdir, "tb_started.txt"), "w") as f:
  f.write("%d %s" % (os.getpid(), a.port))
while True:
  time.sleep(0.2)
"""


def test_tensorboard_spawned_on_chief_and_killed_on_shutdown(
    tmp_path, monkeypatch):
  """tensorboard=True spawns the discovered binary on the chief with the
  requested port, tensorboard_url() plumbs through cluster_info, and
  shutdown kills the server (parity: TFSparkNode.py:292-329, 619-625;
  TFCluster.tensorboard_url, TFCluster.py:207-212)."""
  import time
  from tensorflowonspark_tpu.utils.hostinfo import get_free_port

  fake_bin = tmp_path / "bin"
  fake_bin.mkdir()
  (fake_bin / "tensorboard").write_text(_FAKE_TB)
  log_dir = tmp_path / "logs"
  log_dir.mkdir()
  port = get_free_port()
  monkeypatch.setenv("PATH",
                     str(fake_bin) + os.pathsep + os.environ.get("PATH", ""))
  monkeypatch.setenv("TENSORBOARD_PORT", str(port))

  engine = LocalEngine(num_executors=2)
  try:
    c = tos_cluster.run(engine, lambda args, ctx: None,
                        input_mode=InputMode.FILES, tensorboard=True,
                        log_dir=str(log_dir), reservation_timeout=30)
    url = c.tensorboard_url()
    assert url is not None and url.endswith(":%d" % port), url

    started = log_dir / "tb_started.txt"
    deadline = time.time() + 20
    while not started.exists() and time.time() < deadline:
      time.sleep(0.2)
    assert started.exists(), "fake tensorboard never started"
    tb_pid, tb_port = started.read_text().split()
    assert tb_port == str(port)
    os.kill(int(tb_pid), 0)        # alive while the cluster runs

    c.shutdown(timeout=120)
    killed = log_dir / "tb_killed.txt"
    deadline = time.time() + 20
    while not killed.exists() and time.time() < deadline:
      time.sleep(0.2)
    assert killed.exists(), "shutdown did not SIGTERM the tensorboard"
  finally:
    engine.stop()


def test_independent_jax_nodes(engine):
  """Each node runs a small real JAX computation (parity :16-27)."""

  def main_fn(args, ctx):
    import jax.numpy as jnp
    result = float(jnp.square(jnp.arange(4)).sum())  # 0+1+4+9
    with open("result.txt", "w") as f:
      f.write("%d:%s:%f" % (ctx.executor_id, ctx.job_name, result))

  c = tos_cluster.run(engine, main_fn, tf_args=None,
                      input_mode=InputMode.FILES, reservation_timeout=30)
  c.shutdown(timeout=120)

  for slot in range(2):
    path = os.path.join(engine.executor_workdir(slot), "result.txt")
    assert os.path.exists(path)
    eid, job, val = open(path).read().split(":")
    assert job == "worker"
    assert float(val) == 14.0


def test_train_unroll_knob_reaches_every_node(engine):
  """cluster.run(train_unroll=K) exports TOS_TRAIN_UNROLL into each node
  process, so make_train_loop/slab_batches default to the cluster's K
  without per-fn plumbing (and an explicit argument still wins)."""

  def main_fn(args, ctx):
    import os as _os
    from tensorflowonspark_tpu.parallel.sharding import (ENV_TRAIN_UNROLL,
                                                         resolve_unroll)
    with open("unroll.txt", "w") as f:
      f.write("%s|%d|%d" % (_os.environ.get(ENV_TRAIN_UNROLL),
                            resolve_unroll(), resolve_unroll(2)))

  c = tos_cluster.run(engine, main_fn, input_mode=InputMode.FILES,
                      reservation_timeout=30, train_unroll=4)
  assert c.cluster_meta["train_unroll"] == 4
  c.shutdown(timeout=120)
  for slot in range(2):
    path = os.path.join(engine.executor_workdir(slot), "unroll.txt")
    assert open(path).read() == "4|4|2"


def test_train_unroll_validation(engine):
  with pytest.raises(ValueError):
    tos_cluster.run(engine, lambda a, c: None, train_unroll=0)


def test_apply_node_env_retracts_only_its_own_export(monkeypatch):
  """A persistent executor must not leak run A's train_unroll into run B
  (which never opted in) — but a USER-set env pin is not ours to pop."""
  from tensorflowonspark_tpu import node
  from tensorflowonspark_tpu.parallel.sharding import ENV_TRAIN_UNROLL
  monkeypatch.delenv(ENV_TRAIN_UNROLL, raising=False)
  node._applied_node_env.clear()
  node._apply_node_env({"train_unroll": 8})       # run A exports
  assert os.environ[ENV_TRAIN_UNROLL] == "8"
  node._apply_node_env({"train_unroll": None})    # run B sets nothing
  assert ENV_TRAIN_UNROLL not in os.environ       # A's export retracted
  monkeypatch.setenv(ENV_TRAIN_UNROLL, "3")       # user's own pin
  node._apply_node_env({"train_unroll": None})
  assert os.environ[ENV_TRAIN_UNROLL] == "3"      # passes through


def test_cluster_spec_and_roles(engine):
  def main_fn(args, ctx):
    with open("spec.txt", "w") as f:
      f.write("%s|%d|%d|%d" % (ctx.job_name, ctx.task_index,
                               ctx.num_processes, ctx.process_id))

  c = tos_cluster.run(engine, main_fn, master_node="chief",
                      input_mode=InputMode.FILES, reservation_timeout=30)
  assert len(c.cluster_info) == 2
  spec_jobs = sorted(n["job_name"] for n in c.cluster_info)
  assert spec_jobs == ["chief", "worker"]
  c.shutdown(timeout=120)

  specs = sorted(open(os.path.join(engine.executor_workdir(s), "spec.txt"))
                 .read() for s in range(2))
  assert specs == ["chief|0|2|0", "worker|0|2|1"]


def test_inference_roundtrip_sum_squares(engine):
  """ENGINE-mode inference over 200 rows in 10 partitions (parity :29-48)."""

  def main_fn(args, ctx):
    feed = ctx.get_data_feed(train_mode=False)
    while not feed.should_stop():
      batch = feed.next_batch(32)
      if batch:
        feed.batch_results([x * x for x in batch])

  c = tos_cluster.run(engine, main_fn, input_mode=InputMode.ENGINE,
                      reservation_timeout=30)
  data = list(range(200))
  partitions = [data[i::10] for i in range(10)]
  results = c.inference(partitions, feed_timeout=60)
  c.shutdown(timeout=120)
  assert len(results) == 200
  assert sum(results) == sum(x * x for x in data)


def test_inference_lazy_streams_without_driver_collect(engine):
  """collect=False streams ≥10k inference rows through the driver without
  ever materializing the full result list (parity: reference
  TFCluster.inference returning a lazy RDD, TFCluster.py:96-115)."""

  def main_fn(args, ctx):
    feed = ctx.get_data_feed(train_mode=False)
    while not feed.should_stop():
      batch = feed.next_batch(256)
      if batch:
        feed.batch_results([x + 1 for x in batch])

  c = tos_cluster.run(engine, main_fn, input_mode=InputMode.ENGINE,
                      reservation_timeout=30)
  n_rows, n_parts = 12000, 24
  pulled = []

  def parts():
    for p in range(n_parts):
      pulled.append(p)
      yield list(range(p * 500, (p + 1) * 500))

  lazy = c.inference(parts(), feed_timeout=60, collect=False)
  assert not isinstance(lazy, list)
  total, count = 0, 0
  first_row_pull_count = None
  for row in lazy:
    if first_row_pull_count is None:
      first_row_pull_count = len(pulled)
    total += row
    count += 1
  c.shutdown(timeout=120)
  assert count == n_rows
  assert total == sum(range(n_rows)) + n_rows
  assert first_row_pull_count <= engine.num_executors + 2, \
      "lazy inference pre-pulled the whole dataset onto the driver"


def test_default_transport_is_shm_on_local_engine(engine):
  """feed_transport="auto" (the default) resolves to the shared-memory
  ring on engines whose executors share this host; 32k rows flow through
  it end-to-end."""
  from tensorflowonspark_tpu.control import shmring
  if not shmring.available():
    pytest.skip("native shmring unavailable")

  def main_fn(args, ctx):
    feed = ctx.get_data_feed(train_mode=True)
    total = 0
    while not feed.should_stop():
      for x in feed.next_batch(512):
        total += x
    with open("total32k.txt", "w") as f:
      f.write(str(total))

  c = tos_cluster.run(engine, main_fn, input_mode=InputMode.ENGINE,
                      reservation_timeout=30)
  assert c.cluster_meta["feed_transport"] == "shm"
  n = 32_000
  data = list(range(n))
  c.train([data[i::16] for i in range(16)], num_epochs=1, feed_timeout=120)
  c.shutdown(timeout=120)
  totals = []
  for slot in range(2):
    path = os.path.join(engine.executor_workdir(slot), "total32k.txt")
    if os.path.exists(path):
      totals.append(int(open(path).read()))
  assert sum(totals) == sum(range(n))


def test_remote_feeder_falls_back_to_hub_queue(engine):
  """Multi-host story: a feeder that cannot reach a node's shm ring feeds
  through the hub queue, and the node's DualInput consumer drains BOTH
  channels. Simulated by injecting rows straight into the hub queue (what
  input_channel's fallback does on a remote host) while the normal feed
  uses the ring."""
  from tensorflowonspark_tpu.control import feedhub, shmring
  if not shmring.available():
    pytest.skip("native shmring unavailable")

  def main_fn(args, ctx):
    feed = ctx.get_data_feed(train_mode=True)
    total = 0
    while not feed.should_stop():
      for x in feed.next_batch(64):
        total += x
    with open("total_dual.txt", "w") as f:
      f.write(str(total))

  c = tos_cluster.run(engine, main_fn, input_mode=InputMode.ENGINE,
                      reservation_timeout=30)
  assert c.cluster_meta["feed_transport"] == "shm"
  # "remote" rows: put into every node's hub queue directly, bypassing
  # the ring — exactly the remote-feeder fallback path
  remote_rows = list(range(1000, 1200))
  for n in c.cluster_info:
    hub = feedhub.connect(tuple(n["hub_addr"]),
                          c.cluster_meta["authkey"])
    hub.get_queue("input").put_many(remote_rows, block=True, timeout=30)
  # normal (ring) feed + end-of-feed markers via shutdown
  local_rows = list(range(200))
  c.train([local_rows[i::4] for i in range(4)], num_epochs=1,
          feed_timeout=60)
  c.shutdown(timeout=120)

  totals = []
  for slot in range(2):
    path = os.path.join(engine.executor_workdir(slot), "total_dual.txt")
    if os.path.exists(path):
      totals.append(int(open(path).read()))
  assert sum(totals) == sum(local_rows) + 2 * sum(remote_rows)


@pytest.mark.parametrize("transport", ["queue", "shm"])
def test_train_feed_and_shutdown(engine, transport):
  """ENGINE-mode training feed: every row reaches some worker exactly once
  — on both the queue and shared-memory transports."""
  if transport == "shm":
    from tensorflowonspark_tpu.control import shmring
    if not shmring.available():
      pytest.skip("native shmring unavailable")

  def main_fn(args, ctx):
    feed = ctx.get_data_feed(train_mode=True)
    total = 0
    while not feed.should_stop():
      for x in feed.next_batch(16):
        total += x
    with open("total.txt", "w") as f:
      f.write(str(total))

  c = tos_cluster.run(engine, main_fn, input_mode=InputMode.ENGINE,
                      reservation_timeout=30, feed_transport=transport)
  partitions = [[1] * 10, [2] * 10, [3] * 10, [4] * 10]
  c.train(partitions, num_epochs=2, feed_timeout=60)
  c.shutdown(timeout=120)

  grand = 0
  for slot in range(2):
    path = os.path.join(engine.executor_workdir(slot), "total.txt")
    grand += int(open(path).read())
  assert grand == 2 * (10 + 20 + 30 + 40)


def test_exception_during_feeding(engine):
  """A worker failing mid-feed must fail the train job (parity :50-68)."""

  def main_fn(args, ctx):
    feed = ctx.get_data_feed(train_mode=True)
    feed.next_batch(1)
    raise RuntimeError("intentional worker failure")

  c = tos_cluster.run(engine, main_fn, input_mode=InputMode.ENGINE,
                      reservation_timeout=30)
  with pytest.raises((RuntimeError, TimeoutError),
                     match="worker error|feed timeout"):
    c.train([[1] * 50 for _ in range(4)], feed_timeout=15)
  with pytest.raises(RuntimeError):
    c.shutdown(timeout=120)


def test_late_exception_after_feeding(engine):
  """An error after feeding completes must surface at shutdown with
  grace_secs (parity :70-91)."""

  def main_fn(args, ctx):
    feed = ctx.get_data_feed(train_mode=True)
    while not feed.should_stop():
      feed.next_batch(16)
    raise RuntimeError("intentional late failure")

  c = tos_cluster.run(engine, main_fn, input_mode=InputMode.ENGINE,
                      reservation_timeout=30)
  c.train([[1] * 5, [2] * 5], feed_timeout=60)
  with pytest.raises(RuntimeError, match="late failure|worker error"):
    c.shutdown(grace_secs=1, timeout=120)


def test_shutdown_task_targets_payload_executor(tmp_path, monkeypatch):
  """The engine's shared task queue can place BOTH shutdown tasks on one
  executor (whichever frees up first). The end-of-feed marker must reach
  the hub of the executor named in the partition payload — not the hub of
  the slot the task happens to occupy — or the untargeted node never sees
  its marker and hangs in the feed loop until engine teardown (exposed
  when TCP_NODELAY made node stop fast enough for placements to collide)."""
  from tensorflowonspark_tpu import node as node_mod
  from tensorflowonspark_tpu.control import feedhub
  from tensorflowonspark_tpu.utils import hostinfo

  authkey = b"k"
  hubs = [feedhub.start(authkey, ["input", "error"], mode="local")
          for _ in range(2)]
  try:
    for h in hubs:
      h.set("state", "stopped")   # nodes already exited; no wait loop
    cluster_info = [
        {"executor_id": i, "job_name": "worker", "task_index": i,
         "hub_addr": list(h.addr)} for i, h in enumerate(hubs)]
    wd = tmp_path / "exec0"       # this task occupies executor 0's slot...
    wd.mkdir()
    hostinfo.write_executor_id(0, str(wd))
    monkeypatch.chdir(wd)

    fn = node_mod.make_shutdown_fn(cluster_info, {"authkey": authkey})
    # ...but its payload targets executor 1: the marker must reach hub 1
    assert fn(iter([1])) == [1]
    assert hubs[1].get_queue("input").get_many(1, block=False) == [None]
    assert hubs[0].get_queue("input").qsize() == 0
    # a correctly-placed task (payload matches the slot) marks its own hub
    assert fn(iter([0])) == [0]
    assert hubs[0].get_queue("input").get_many(1, block=False) == [None]
  finally:
    for h in hubs:
      h.shutdown()


def test_port_reservation_semantics(engine):
  """release_port=False keeps the node port reserved until user code releases
  it (parity :93-121)."""

  def main_fn(args, ctx):
    import socket
    assert ctx.tmp_socket is not None
    port = ctx.tmp_socket.getsockname()[1]
    probe = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    bind_failed = False
    try:
      probe.bind(("", port))
    except OSError:
      bind_failed = True
    finally:
      probe.close()
    ctx.release_port()
    probe2 = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    probe2.bind(("", port))  # must succeed now
    probe2.close()
    with open("ports.txt", "w") as f:
      f.write(str(bind_failed))

  c = tos_cluster.run(engine, main_fn, input_mode=InputMode.FILES,
                      release_port=False, reservation_timeout=30)
  c.shutdown(timeout=120)
  for slot in range(2):
    content = open(os.path.join(engine.executor_workdir(slot),
                                "ports.txt")).read()
    assert content == "True"


def test_ps_evaluator_lifecycle():
  """ps + evaluator sidecars park on the control queue and stop on driver
  signal (parity: TFSparkNode.py:441-458, TFCluster.py:186-194)."""
  engine = LocalEngine(num_executors=3)
  try:
    def main_fn(args, ctx):
      with open("role.txt", "w") as f:
        f.write("%s:%d" % (ctx.job_name, ctx.task_index))

    c = tos_cluster.run(engine, main_fn, num_ps=1, eval_node=True,
                        input_mode=InputMode.FILES, reservation_timeout=30)
    jobs = sorted(n["job_name"] for n in c.cluster_info)
    assert jobs == ["evaluator", "ps", "worker"]
    c.shutdown(timeout=120)
    roles = set()
    for slot in range(3):
      roles.add(open(os.path.join(engine.executor_workdir(slot),
                                  "role.txt")).read().split(":")[0])
    assert roles == {"ps", "evaluator", "worker"}
  finally:
    engine.stop()


def test_engine_reuse_two_clusters(engine):
  """A second cluster on the same engine must reclaim the previous run's
  stale hubs (different authkey) instead of failing bring-up."""

  def main_fn(args, ctx):
    with open("gen.txt", "a") as f:
      f.write("x")

  for generation in range(2):
    c = tos_cluster.run(engine, main_fn, input_mode=InputMode.FILES,
                        reservation_timeout=30)
    c.shutdown(timeout=120)
  for slot in range(2):
    content = open(os.path.join(engine.executor_workdir(slot),
                                "gen.txt")).read()
    assert content == "xx"


def test_early_bringup_failure_surfaces_fast():
  """A node failing before registration must abort run() with its traceback
  well before the reservation timeout."""
  import time

  def main_fn(args, ctx):
    pass

  # sabotage node bring-up inside the executors: the pinned node port is
  # unparseable, so every node task raises before registering
  bad = LocalEngine(num_executors=2,
                    env={"TOS_TPU_NODE_PORT": "notaport"})
  try:
    t0 = time.time()
    with pytest.raises(RuntimeError,
                       match="(?s)cluster startup aborted.*notaport"):
      tos_cluster.run(bad, main_fn, input_mode=InputMode.FILES,
                      reservation_timeout=300)
    assert time.time() - t0 < 60
  finally:
    bad.stop()


def test_shm_feed_transport_roundtrip(engine):
  """ENGINE mode over the native shared-memory ring: train + inference
  round-trips must behave identically to the queue transport."""
  from tensorflowonspark_tpu.control import shmring
  if not shmring.available():
    pytest.skip("native shmring unavailable")

  def main_fn(args, ctx):
    feed = ctx.get_data_feed(train_mode=False)
    while not feed.should_stop():
      batch = feed.next_batch(32)
      if batch:
        feed.batch_results([x * 3 for x in batch])

  c = tos_cluster.run(engine, main_fn, input_mode=InputMode.ENGINE,
                      reservation_timeout=30, feed_transport="shm")
  assert all(n is not None for n in c.cluster_info)
  data = list(range(150))
  results = c.inference([data[i::6] for i in range(6)], feed_timeout=60)
  c.shutdown(timeout=120)
  assert sorted(results) == sorted(x * 3 for x in data)


def test_train_stream_with_stop_signal(engine):
  """Streaming feed rounds end on the graceful stop signal (parity:
  DStream feeding + stop_streaming, reference TFCluster.py:83-85,150-152)."""

  def main_fn(args, ctx):
    feed = ctx.get_data_feed(train_mode=True)
    total = 0
    while not feed.should_stop():
      for x in feed.next_batch(16):
        total += x
    with open("stream_total.txt", "w") as f:
      f.write(str(total))

  c = tos_cluster.run(engine, main_fn, input_mode=InputMode.ENGINE,
                      reservation_timeout=30)

  def stream():
    for round_no in range(100):       # "unbounded" source
      if round_no == 3:
        # a remote client sends the stop signal (stop_streaming parity)
        from tensorflowonspark_tpu.control.rendezvous import Client
        Client(tuple(c.server_addr)).request_stop()
      yield [[1] * 10, [1] * 10]

  rounds = c.train_stream(stream(), feed_timeout=60)
  assert rounds <= 4
  c.shutdown(timeout=120)
  grand = sum(int(open(os.path.join(engine.executor_workdir(s),
                                    "stream_total.txt")).read())
              for s in range(2))
  assert grand == rounds * 20


def test_driver_ps_nodes():
  """ps nodes hosted on the driver machine (parity: TFCluster.py:298-316):
  cluster_size = engine executors + num_ps."""
  engine = LocalEngine(num_executors=2)
  try:
    def main_fn(args, ctx):
      with open("role.txt", "w") as f:
        f.write("%s:%d" % (ctx.job_name, ctx.task_index))

    c = tos_cluster.run(engine, main_fn, num_executors=3, num_ps=1,
                        driver_ps_nodes=True,
                        input_mode=InputMode.FILES, reservation_timeout=30)
    jobs = sorted(n["job_name"] for n in c.cluster_info)
    assert jobs == ["ps", "worker", "worker"]
    assert len(c.driver_ps_procs) == 1
    c.shutdown(timeout=120)
    assert not c.driver_ps_procs[0].is_alive()
    # both engine executors ran workers (ps lived on the driver)
    for slot in range(2):
      role = open(os.path.join(engine.executor_workdir(slot),
                               "role.txt")).read()
      assert role.startswith("worker")
  finally:
    engine.stop()


def test_driver_ps_requires_files_mode(engine):
  with pytest.raises(ValueError, match="driver_ps_nodes"):
    tos_cluster.run(engine, lambda a, c: None, num_ps=1,
                    driver_ps_nodes=True, input_mode=InputMode.ENGINE)


def test_validation_errors(engine):
  with pytest.raises(AssertionError, match="at least one worker"):
    tos_cluster.run(engine, lambda a, c: None, num_ps=2,
                    input_mode=InputMode.FILES)
  with pytest.raises(ValueError, match="executors"):
    tos_cluster.run(engine, lambda a, c: None, num_executors=5)


def test_inference_over_lazy_tfrecord_partitions(engine, tmp_path):
  """load_tfrecords(lazy=True) handles feed straight into the cluster:
  the feeder resolves each callable ON the executor
  (node._materialize_partition), so TFRecord decode never happens on the
  driver — the reference's executor-side loadTFRecords parse
  (dfutil.py:44-81) composed with InputMode.SPARK feeding."""
  import os as _os
  from tensorflowonspark_tpu.data import dfutil
  from tensorflowonspark_tpu.data.schema import parse_schema

  sch = parse_schema("struct<v:long>")
  src = [[(f * 10 + i,) for i in range(5)] for f in range(4)]
  dfutil.save_as_tfrecords(src, sch, str(tmp_path / "d"))
  marker = str(tmp_path / "decoded_pid")

  parts, _ = dfutil.load_tfrecords(str(tmp_path / "d"), schema=sch,
                                   lazy=True)

  def spying(i, p):
    # wrap each handle so the test can observe WHERE it ran
    def _run():
      with open("%s.%d" % (marker, i), "w") as fh:
        fh.write(str(_os.getpid()))
      return (row[0] for row in p())
    return _run

  parts = [spying(i, p) for i, p in enumerate(parts)]

  def main_fn(args, ctx):
    feed = ctx.get_data_feed(train_mode=False)
    while not feed.should_stop():
      batch = feed.next_batch(16)
      if batch:
        feed.batch_results([x * 2 for x in batch])

  c = tos_cluster.run(engine, main_fn, input_mode=InputMode.ENGINE,
                      reservation_timeout=30)
  results = c.inference(parts, feed_timeout=60)
  c.shutdown(timeout=120)
  assert sorted(results) == sorted(r[0] * 2 for p in src for r in p)
  import glob as _glob
  pids = {open(m).read() for m in _glob.glob(marker + ".*")}
  assert pids and str(_os.getpid()) not in pids, \
      "lazy partitions were materialized on the driver"


def test_quarantine_drain_keeps_markers_for_inference_feeds():
  """The supervisor's dead-hub drain preserves EndPartition markers when
  the active feed is an inference feed (cluster_meta carries feed_kind),
  so a refeed keeps per-partition result alignment — and keeps dropping
  them for train feeds."""
  from tensorflowonspark_tpu.cluster import ClusterSupervisor
  from tensorflowonspark_tpu.control import feedhub
  from tensorflowonspark_tpu.control.marker import EndPartition
  from tensorflowonspark_tpu.node import put_rows_chunk

  def _drain(feed_kind):
    hub = feedhub.start(b"k", ["input", "output", "error"], mode="remote")
    try:
      q = hub.get_queue("input")
      put_rows_chunk(q, [1, 2], timeout=5)
      q.put(EndPartition())
      put_rows_chunk(q, [3], timeout=5)
      meta = {"authkey": b"k", "input_mode": InputMode.ENGINE,
              "queues": ["input", "output", "error"],
              "feed_kind": feed_kind}
      sup = ClusterSupervisor(engine=None, server=None, node_job=None,
                              cluster_meta=meta, cluster_info=[],
                              engine_ids=[], tf_status={"error": None})
      return sup._quarantine_dead_hub(
          {"executor_id": 0, "hub_addr": list(hub.addr)})
    finally:
      hub.shutdown()

  pending = _drain("inference")
  assert [r for r in pending["input"] if not isinstance(r, EndPartition)] \
      == [1, 2, 3]
  assert isinstance(pending["input"][2], EndPartition)  # position preserved
  pending = _drain("train")
  assert pending["input"] == [1, 2, 3]


# --- no fallback that hides the device (PR 21) ------------------------------


def _noop_main(args, ctx):
  pass


def test_requested_shm_transport_unavailable_raises_at_driver():
  """feed_transport="shm" ASKED for and unavailable is an error that
  reaches the driver — only "auto" may settle for the queue."""
  def lose_the_ring(_it):
    # this persistent executor process now behaves like a host whose
    # native ring cannot be built (no g++, no prebuilt .so)
    from tensorflowonspark_tpu.control import shmring
    shmring._lib, shmring._lib_tried = None, True
    return [shmring.available()]

  e = LocalEngine(num_executors=1)
  try:
    assert e.run_on_executors(lose_the_ring).wait(timeout=60) == [[False]]
    with pytest.raises(Exception, match="feed_transport='shm' was requested"):
      tos_cluster.run(e, _noop_main, input_mode=InputMode.ENGINE,
                      feed_transport="shm", reservation_timeout=30)
    # "auto" may still choose: same executor, the queue serves
    c = tos_cluster.run(e, _noop_main, input_mode=InputMode.ENGINE,
                        reservation_timeout=30)
    c.shutdown(timeout=60)
  finally:
    e.stop()


def test_unknown_feed_transport_rejected(engine):
  with pytest.raises(ValueError, match="feed_transport"):
    tos_cluster.run(engine, _noop_main, feed_transport="carrier-pigeon")


def test_chips_per_node_without_topology_raises_at_driver(monkeypatch):
  """Outside TOS_TPU_TEST_MODE, chips_per_node > 0 with no TPU topology
  visible is an error at the driver, not a silently skipped allocation."""
  monkeypatch.delenv("TPU_ACCELERATOR_TYPE", raising=False)
  e = LocalEngine(num_executors=1, env={"TOS_TPU_TEST_MODE": ""})
  try:
    # allocation runs AFTER the reservation (it needs the co-hosted
    # population), so the error surfaces from run() or from shutdown()
    with pytest.raises(Exception, match="no TPU topology is visible"):
      c = tos_cluster.run(e, _noop_main, chips_per_node=1,
                          reservation_timeout=30)
      c.shutdown(timeout=60)
  finally:
    e.stop()


def test_parallel_run_chips_without_topology_raises(monkeypatch):
  from tensorflowonspark_tpu.parallel import runner
  monkeypatch.delenv("TPU_ACCELERATOR_TYPE", raising=False)
  e = LocalEngine(num_executors=1, env={"TOS_TPU_TEST_MODE": ""})
  try:
    with pytest.raises(Exception, match="no TPU topology is visible"):
      runner.run(e, lambda args, ctx: 1, chips_per_node=1, timeout=60)
    # nothing asked, nothing claimed: the same engine still runs tasks
    assert runner.run(e, lambda args, ctx: 1, timeout=60) == [1]
  finally:
    e.stop()


def test_engine_mode_refuses_when_the_executor_holds_the_chip(monkeypatch):
  """One process per chip: an executor that already initialised JAX on the
  TPU cannot hand the chip to the ENGINE-mode child it must spawn — fail
  with a message that says so instead of a hang."""
  import sys
  import types
  from tensorflowonspark_tpu import node as node_mod
  node_mod._refuse_if_chip_held(0)                 # CPU backend: fine
  monkeypatch.setattr(node_mod.platform_env, "backend_initialized",
                      lambda: True)
  fake = types.SimpleNamespace(default_backend=lambda: "tpu")
  monkeypatch.setitem(sys.modules, "jax", fake)
  with pytest.raises(RuntimeError, match="can never take the chip"):
    node_mod._refuse_if_chip_held(3)
