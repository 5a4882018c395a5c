"""Runner ``train_fed``: the cluster's fed training path.

``cluster.run(LocalEngine(1), node_main, ENGINE input, shm ring,
train_unroll=K)``; this (JAX-free) parent feeds seeded token rows with
``c.train``; the node (the one process on the chip) steps
``make_train_loop`` on ``device_prefetch(slab_batches(feed, B))`` and
measures ``--seconds`` of it.  Started as a copy of ``chip_smoke.py``'s
``train_main`` / ``_run_cluster`` / ``phase_train``.

Traffic file keys: ``batch``, ``seq``, ``unroll``, ``prefetch``,
``table_rows``, ``rows_per_partition``, ``max_rows_per_s`` (how much is
offered: the feed must outlast the window at any speed a later PR reaches),
``row_block`` (rows per gradient block of the reference),
``heartbeat_interval`` (the cluster's liveness beat), ``trace_seconds``, ``limits`` (one per compared number).
"""

import json
import math
import os
import sys
import time

from benchmarks.lib import compare
from benchmarks.lib import loader
from benchmarks.lib import traffic as traffic_lib

TIMEOUT_S = 900


def _family(config: dict):
  return loader.load_module("families", config["family"])


# ---------------------------------------------------------------------------
# the node: the process cluster.run spawns, which owns the chip
# ---------------------------------------------------------------------------


class _Tap(object):
  """Host-side view of every slab on its way to the device: keeps the first
  one (the reference follows it), and compares every row with the seeded row
  it has to be, in feed order."""

  def __init__(self, table, seq: int):
    self.table, self.seq = table, seq
    self.first = None
    self.rows = 0
    self.mismatched = 0
    self.partial = 0

  def __call__(self, items):
    import numpy as np
    from tensorflowonspark_tpu.data import readers
    for item in items:
      if not isinstance(item, readers.Slab):
        self.partial += 1            # a partial tail: not expected
        yield item
        continue
      data = np.asarray(item.data)
      if self.first is None:
        self.first = data.copy()
      flat = data.reshape(-1, self.seq)
      want = traffic_lib.expected_rows(self.table, self.rows, len(flat))
      self.mismatched += int((flat != want).any(axis=1).sum())
      self.rows += len(flat)
      yield item


def node_main(spec, ctx):
  """ENGINE-mode node fn (runs in the node process on the chip)."""
  import numpy as np
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu import node as node_mod
  from tensorflowonspark_tpu.data import readers
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib
  from tensorflowonspark_tpu.parallel import sharding as SH
  from tensorflowonspark_tpu.utils import compile_cache
  from benchmarks.lib import device as dev_lib

  counter = dev_lib.CompileCounter()   # node bring-up placed the cache
  dev_lib.cache_every_program()
  dev = dev_lib.device_record(spec["chips"], spec["rehearse"])
  tr, config, seed = spec["traffic"], spec["config"], spec["seed"]
  B, S, K = tr["batch"], tr["seq"], tr["unroll"]
  family = _family(config)

  t0 = time.monotonic()
  cfg = family.program_config(config, S)
  params = family.program_params(seed, config, "float32")
  p0 = jax.tree.map(jnp.copy, params)              # the loop donates its state
  state = family.program_train_state(params, cfg, S)
  mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=-1),
                             devices=jax.local_devices()[:1])
  loop = SH.make_train_loop(family.program_loss_fn(cfg), mesh)
  if loop.unroll != K:
    raise RuntimeError("TOS_TRAIN_UNROLL gave %d, the cell asks %d"
                       % (loop.unroll, K))
  jax.block_until_ready(state)
  state_s = time.monotonic() - t0

  table = traffic_lib.train_table(seed, tr["table_rows"], S,
                                  config["vocab_size"])
  feed = ctx.get_data_feed(train_mode=True)
  tap = _Tap(table, S)
  host_items = tap(readers.slab_batches(feed, B))
  items = iter(readers.device_prefetch(host_items, size=tr["prefetch"]))

  # set-up drives the very object the window then uses through its first
  # dispatch (K steps) on the first fed slab: compile or cache load, and the
  # numbers the reference is compared with
  t0 = time.monotonic()
  state, first_losses = loop(state, next(items))
  first_losses = [float(x) for x in np.asarray(first_losses)]
  first_dispatch_s = time.monotonic() - t0
  mu = family.program_leaf_norms(family.first_moment(state))
  delta = family.program_leaf_norms(
      jax.tree.map(jnp.subtract, state.params, p0))
  del p0, params
  # one more dispatch outside the window: the prefetch queue is full and
  # the second call of the program (no first-call work left) has run
  state, ls = loop(state, next(items))
  np.asarray(ls)

  tracer = dev_lib.Tracer(spec["trace"], os.path.join(spec["run_dir"], "trace"),
                          tr["trace_seconds"])
  ann = jax.profiler.TraceAnnotation
  counter.mark()
  losses, ends, wait_s, pending = [], [], 0.0, None
  t_setup_done = time.time()
  w0 = time.monotonic()
  while time.monotonic() - w0 < spec["seconds"]:
    t = time.monotonic()
    with ann("bench.feed_wait"):
      item = next(items, None)
    wait_s += time.monotonic() - t
    if item is None or not isinstance(item, readers.Slab):
      raise RuntimeError("the feed ran dry or handed a partial slab inside "
                         "the window after %d dispatches" % len(ends))
    with ann("bench.dispatch"):
      state, ls = loop(state, item)
    if pending is not None:
      with ann("bench.loss_fetch"):
        losses.extend(float(x) for x in np.asarray(pending))
      ends.append(time.monotonic() - w0)
    pending = ls
  with ann("bench.loss_fetch"):
    losses.extend(float(x) for x in np.asarray(pending))
  ends.append(time.monotonic() - w0)
  window_s = ends[-1]
  in_window = counter.since_mark()

  # --trace 1: the same loop a few seconds longer, under the profiler
  tracer.start()
  while not tracer.expired():
    with ann("bench.feed_wait"):
      item = next(items)
    with ann("bench.dispatch"):
      state, ls = loop(state, item)
    with ann("bench.loss_fetch"):
      np.asarray(ls)
  tracer.stop()

  peak, mem_stats = dev_lib.memory_peak_bytes(), dev_lib.memory_stats()
  chan = feed._queue_in
  deliveries = dict(chan.deliveries) if isinstance(
      chan, node_mod.DualInput) else {"ring": 0, "queue": -1}
  # No feed.terminate(): its drain can settle while a feeder still holds
  # rows, and that feeder then waits out its whole timeout (PERF.md, Open
  # questions).  The rest of what was offered is read and compared instead,
  # so rows fed = rows stepped + rows prefetched + rows drained, exactly.
  stepped_and_staged = tap.rows
  for _ in host_items:
    pass
  n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(state.params))
  del state, items
  summary = tracer.reduce()

  # the plain reference, after the window and after the program's state is
  # freed: K AdamW steps in float32 on the first slab, from the same seed
  t0 = time.monotonic()
  ref = family.reference_train(
      family.make_weights(seed, config, "float32"), tap.first, config,
      row_block=tr["row_block"])
  reference_s = time.monotonic() - t0
  control = None
  if spec.get("control"):
    # the CONTROL: the reference in fp8 put in the program's place
    low = family.reference_train(
        family.make_weights(seed, config, "float32"), tap.first, config,
        precision="fp8", row_block=tr["row_block"])
    control = dict(
        loss_gap_max=max(abs(a - b) for a, b in zip(low["losses"],
                                                    ref["losses"])),
        first_moment_worst_leaf_gap=compare.worst_leaf_gap(low["mu"],
                                                           ref["mu"]),
        param_change_worst_leaf_gap=compare.worst_leaf_gap(low["delta"],
                                                           ref["delta"]))

  report = dict(
      device=dev, memory_peak_bytes=peak, memory_stats=mem_stats,
      window_s=window_s, dispatches=len(ends), dispatch_ends=ends,
      steps=len(losses), rows_stepped=len(losses) * B,
      tokens=len(losses) * B * S, feed_wait_s=wait_s,
      losses_nonfinite=sum(not math.isfinite(x) for x in losses),
      last_loss=losses[-1], first_losses=first_losses,
      program=dict(mu=mu, delta=delta), reference=ref,
      reference_s=reference_s, control=control, rows_seen=tap.rows,
      rows_drained=tap.rows - stepped_and_staged,
      rows_mismatched=tap.mismatched, partial_items=tap.partial,
      deliveries=deliveries, n_params=n_params,
      compile=counter.record(), compiles_in_window=in_window,
      state_s=state_s, first_dispatch_s=first_dispatch_s,
      t_window_start=t_setup_done, trace_summary=summary,
      cache_dir=compile_cache.cache_dir(), pid=os.getpid())
  path = os.path.join(spec["run_dir"], "node_%d.json" % ctx.executor_id)
  with open(path + ".tmp", "w") as f:
    json.dump(report, f)
  os.replace(path + ".tmp", path)


# ---------------------------------------------------------------------------
# the parent: orchestration only, never JAX
# ---------------------------------------------------------------------------


def _pid_gone(pid: int) -> bool:
  try:
    with open("/proc/%d/stat" % pid) as f:
      return f.read().rsplit(")", 1)[1].split()[0] == "Z"
  except OSError:
    return True


def _wait_gone(pids, what: str, timeout: float = 30.0) -> None:
  deadline = time.time() + timeout
  while time.time() < deadline:
    if all(_pid_gone(p) for p in pids):
      return
    time.sleep(0.2)
  raise RuntimeError("%s still running: %r"
                     % (what, [p for p in pids if not _pid_gone(p)]))


def checks_from(rep: dict, limits: dict) -> list:
  """Every number compared, beside its limit."""
  ref, prog = rep["reference"], rep["program"]
  loss_gap = max(abs(a - b) for a, b in zip(rep["first_losses"],
                                            ref["losses"]))
  return [
      compare.check("losses_nonfinite", rep["losses_nonfinite"], 0, "eq"),
      compare.check("rows_mismatched", rep["rows_mismatched"], 0, "eq"),
      compare.check("rows_fed_minus_seen",
                    rep["rows_offered"] - rep["rows_seen"], 0, "eq"),
      compare.check("partial_slabs", rep["partial_items"], 0, "eq"),
      compare.check("ring_deliveries", rep["deliveries"]["ring"], 1, "ge"),
      compare.check("queue_deliveries", rep["deliveries"]["queue"], 0, "eq"),
      compare.check("loss_gap_max", loss_gap, limits["loss_gap_max"]),
      compare.check("first_moment_worst_leaf_gap",
                    compare.worst_leaf_gap(prog["mu"], ref["mu"]),
                    limits["first_moment_worst_leaf_gap"]),
      compare.check("param_change_worst_leaf_gap",
                    compare.worst_leaf_gap(prog["delta"], ref["delta"]),
                    limits["param_change_worst_leaf_gap"]),
  ]


def run(spec: dict) -> dict:
  from tensorflowonspark_tpu import cluster
  from tensorflowonspark_tpu.cluster import InputMode
  from tensorflowonspark_tpu.control import shmring
  from tensorflowonspark_tpu.engine import LocalEngine
  tr, config = spec["traffic"], spec["config"]
  # what runs is what git holds: the native ring is built by this run
  if not shmring.rebuild():
    raise RuntimeError("native/shmring.cpp did not build")
  B, K = tr["batch"], tr["unroll"]
  per_part = tr["rows_per_partition"]
  if per_part % (B * K):
    raise ValueError("rows_per_partition must be whole slabs of %d rows"
                     % (B * K))
  offered = tr["max_rows_per_s"] * (spec["seconds"] + tr["extra_seconds"])
  n_parts = max(2, int(math.ceil(offered / per_part)))
  table = traffic_lib.train_table(spec["seed"], tr["table_rows"], tr["seq"],
                                  config["vocab_size"])
  parts = traffic_lib.train_partitions(table, per_part, n_parts)

  engine = LocalEngine(num_executors=1)
  executor_pids = [p.pid for p in engine._procs]
  try:
    c = cluster.run(engine, node_main, tf_args=spec, max_restarts=0,
                    reservation_timeout=300, input_mode=InputMode.ENGINE,
                    feed_transport="shm", train_unroll=K,
                    heartbeat_interval=tr["heartbeat_interval"])
    c.train(parts, num_epochs=1, feed_timeout=TIMEOUT_S)
    c.shutdown(timeout=TIMEOUT_S)
    if c.supervisor.restarts != {}:
      raise RuntimeError("a node was relaunched: %r" % c.supervisor.restarts)
  finally:
    engine.stop()
  _wait_gone(executor_pids, "LocalEngine executors")
  with open(os.path.join(spec["run_dir"], "node_0.json")) as f:
    rep = json.load(f)
  _wait_gone([rep["pid"]], "train node process")
  if "jax" in sys.modules and not spec["rehearse"]:
    raise RuntimeError("the parent touched JAX")

  comp = rep["compile"]
  rep["rows_offered"] = n_parts * per_part
  rep["checks"] = checks_from(rep, tr["limits"])
  rep["attempted"] = rep["dispatches"]
  rep["failed"] = 0
  rep["setup_s"] = rep["t_window_start"] - spec["t_start"]
  rep["end_to_end"] = dict(
      train_tok_s=rep["tokens"] / rep["window_s"], setup_s=rep["setup_s"])
  rep["cell_shape"] = dict(batch=B, seq=tr["seq"], unroll=K,
                           layers=config["n_layer"], d_model=config["n_embd"],
                           heads=config["n_head"])
  rep["notes"] = [
      "window %.3f s, %d dispatches of %d steps, %d rows stepped in it, %d "
      "drained after it, %d seen of %d fed"
      % (rep["window_s"], rep["dispatches"], K, rep["rows_stepped"],
         rep["rows_drained"], rep["rows_seen"], rep["rows_offered"]),
      "compilations inside the window %d (expected 0); compiles %d in "
      "%.1f s, cache hits %d misses %d; state %.1f s, first dispatch %.1f s"
      % (rep["compiles_in_window"], comp["compiles"], comp["compile_s"],
         comp["cache_hits"], comp["cache_misses"], rep["state_s"],
         rep["first_dispatch_s"]),
      "deliveries ring %d queue %d; feed wait %.3f s; reference %.1f s "
      "(after the window, not in setup_s); last loss %.4f; parameters %d"
      % (rep["deliveries"]["ring"], rep["deliveries"]["queue"],
         rep["feed_wait_s"], rep["reference_s"], rep["last_loss"],
         rep["n_params"]),
      "memory_stats %s" % json.dumps(rep["memory_stats"], sort_keys=True),
  ]
  if rep.get("control"):
    rep["notes"].append("CONTROL (fp8 reference in the program's place): %s"
                        % json.dumps(rep["control"], sort_keys=True))
  return rep
