"""The declarative autotuned input pipeline (data/datapipe.py).

Four layers:

1. graph VOCABULARY over ``from_chunks`` — map/filter/shuffle/batch/
   slab/prefetch on both the columnar fast path and the row fallback,
   with the marker semantics pinned (end-of-feed partial batch,
   ``EndPartition`` skip in train / boundary in inference, inline
   markers in legacy row lists);
2. INTERLEAVE — deterministic round-robin order, throughput-mode
   completeness, cycle limiting, pure-source validation;
3. the DETERMINISM CONTRACT — ``from_feed(feed).slab(B, K)`` against a
   real feed hub yields byte-identical batches to
   ``data.readers.slab_batches(feed, B, K)`` (end-of-feed tail split
   and ``EndPartition`` skip included), and drives
   ``make_train_loop(unroll=K)`` to a bit-identical loss/param
   trajectory — the PR 9 contract composed through the graph, with the
   autotuner LIVE;
4. the EXECUTOR — autotune moves (worker add on the hot stage, order
   still pinned), structured events + counters, nested
   ``stats_snapshot`` (the PR 4 snapshot-subtract rule over per-stage
   dicts), worker-error propagation, and bounded hand-off waits.
"""

import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tensorflowonspark_tpu.control import feedhub
from tensorflowonspark_tpu.control.chunkcodec import ColumnChunk
from tensorflowonspark_tpu.control.marker import EndPartition
from tensorflowonspark_tpu.data import datapipe
from tensorflowonspark_tpu.data.datapipe import Dataset
from tensorflowonspark_tpu.data.readers import Slab, slab_batches
from tensorflowonspark_tpu.datafeed import DataFeed
from tensorflowonspark_tpu.node import put_rows_chunk
from tensorflowonspark_tpu.obs import metrics as obs_metrics


def _chunks(n_chunks=5, rows=4, width=4):
  """Homogeneous (vec, label) row chunks with global-index labels."""
  return [[(np.full(width, rows * c + i, np.float32), rows * c + i)
           for i in range(rows)] for c in range(n_chunks)]


def _labels(batches):
  out = []
  for b in batches:
    y = b.data["y"] if isinstance(b, Slab) else b["y"]
    out.extend(np.asarray(y).reshape(-1).tolist())
  return out


@pytest.fixture()
def hub():
  h = feedhub.start(b"k", ["input", "output", "error"], mode="local")
  yield h
  h.shutdown()


class TestVocabulary:
  def test_batch_sizes_and_order(self):
    got = list(Dataset.from_chunks(_chunks(), columns=["x", "y"])
               .batch(6).batches())
    assert [len(b["x"]) for b in got] == [6, 6, 6, 2]
    assert _labels(got) == list(range(20))

  def test_columnar_map_and_filter(self):
    ds = (Dataset.from_chunks(_chunks(), columns=["x", "y"])
          .map(lambda x, y: (x * 2.0, y), columnar=True)
          .filter(lambda x, y: y % 2 == 0, columnar=True)
          .batch(4))
    got = list(ds.batches())
    assert _labels(got) == list(range(0, 20, 2))
    assert got[0]["x"][1][0] == 4.0          # row 2 doubled

  def test_row_map_recolumnarizes(self):
    ds = (Dataset.from_chunks(_chunks(), columns=["x", "y"])
          .map(lambda r: (r[0] + 1.0, r[1] + 100))
          .batch(20))
    got = list(ds.batches())
    assert _labels(got) == [100 + i for i in range(20)]
    # homogeneous row-map results re-entered the columnar plane: the
    # batch is a stacked ndarray, not a python list
    assert isinstance(got[0]["x"], np.ndarray)
    assert got[0]["x"].shape == (20, 4)

  def test_row_filter(self):
    ds = (Dataset.from_chunks(_chunks(), columns=["x", "y"])
          .filter(lambda r: r[1] < 7)
          .batch(10))
    assert _labels(list(ds.batches())) == list(range(7))

  def test_map_changing_column_count(self):
    ds = (Dataset.from_chunks(_chunks(), columns=["a", "b", "c"])
          .map(lambda x, y: (x, y, y * 10), columnar=True)
          .batch(5))
    got = list(ds.batches())
    assert np.array_equal(got[0]["c"], got[0]["b"] * 10)

  def test_shuffle_deterministic_per_seed(self):
    def run(seed):
      return _labels(list(Dataset.from_chunks(_chunks(), columns=["x", "y"])
                          .shuffle(8, seed=seed).batch(20).batches()))
    a, b, c = run(3), run(3), run(4)
    assert a == b
    assert a != c
    assert sorted(a) == list(range(20))
    assert a != list(range(20))        # it actually shuffled

  def test_shuffle_flushes_at_partition_boundary(self):
    """Rows must not cross an EndPartition: inference batches stay
    partition-aligned even through a shuffle."""
    chunks = _chunks(4)
    src = [chunks[0], chunks[1], EndPartition(), chunks[2], chunks[3]]
    got = list(Dataset.from_chunks(src, columns=["x", "y"],
                                   train_mode=False)
               .shuffle(64, seed=0).batch(100).batches())
    assert sorted(_labels(got[:1])) == list(range(8))
    assert sorted(_labels(got[1:])) == list(range(8, 16))

  def test_end_partition_train_skip_and_inference_boundary(self):
    chunks = _chunks(2)
    src = [chunks[0], EndPartition(), chunks[1]]
    train = list(Dataset.from_chunks(list(src), columns=["x", "y"])
                 .batch(8).batches())
    assert [len(b["x"]) for b in train] == [8]
    infer = list(Dataset.from_chunks(list(src), columns=["x", "y"],
                                     train_mode=False).batch(8).batches())
    assert [len(b["x"]) for b in infer] == [4, 4]

  def test_inline_markers_in_legacy_row_lists(self):
    """Raw put_many streams carry markers INSIDE row lists; the source
    splits them so batch semantics match the DataFeed row path."""
    rows = [(np.full(2, i, np.float32), i) for i in range(8)]
    src = [rows[:3] + [EndPartition()] + rows[3:6], rows[6:] + [None]]
    infer = list(Dataset.from_chunks(src, columns=["x", "y"],
                                     train_mode=False).batch(10).batches())
    assert _labels(infer) == list(range(8))
    assert [len(b["x"]) for b in infer] == [3, 5]

  def test_slab_full_and_tail_split(self):
    got = list(Dataset.from_chunks(_chunks(), columns=["x", "y"])
               .slab(2, 4).batches())
    assert isinstance(got[0], Slab) and got[0].data["x"].shape == (4, 2, 4)
    assert isinstance(got[1], Slab)
    # 20 rows: two full slabs (16) + a 4-row tail split into 2-row
    # per-step batches — slab_batches order
    assert [isinstance(g, Slab) for g in got] == [True, True, False, False]
    assert _labels(got) == list(range(20))

  def test_single_column_no_names(self):
    src = [[np.full(3, i, np.float32) for i in range(4 * c, 4 * c + 4)]
           for c in range(2)]
    got = list(Dataset.from_chunks(src).batch(8).batches())
    assert isinstance(got[0], np.ndarray) and got[0].shape == (8, 3)

  def test_multi_column_no_names_yields_tuples(self):
    got = list(Dataset.from_chunks(_chunks()).batch(5).batches())
    assert isinstance(got[0], tuple) and len(got[0]) == 2

  def test_dtype_applies(self):
    got = list(Dataset.from_chunks(_chunks(), columns=["x", "y"])
               .batch(5, dtype="float64").batches())
    assert got[0]["x"].dtype == np.float64

  def test_terminal_validation(self):
    ds = Dataset.from_chunks(_chunks(), columns=["x", "y"]).batch(4)
    with pytest.raises(ValueError):
      ds.map(lambda r: r)
    with pytest.raises(ValueError):
      list(ds.chunks())
    with pytest.raises(ValueError):
      list(Dataset.from_chunks(_chunks()).batches())

  def test_prefetch_sets_declared_depth(self):
    ds = (Dataset.from_chunks(_chunks(), columns=["x", "y"])
          .map(lambda r: r).prefetch(7).batch(4).prefetch(5))
    ex = datapipe.GraphExecutor(ds)
    try:
      assert ex._stages[0].name == "map0"
      # depth after map0 (its OUT buffer = assemble's IN buffer)
      assert ex._stages[1].inbuf.capacity == 7
      assert ex._buffers[-1].capacity == 5
    finally:
      ex.stop()

  def test_transform_only_graph_chunks(self):
    items = list(Dataset.from_chunks(_chunks(2))
                 .map(lambda x, y: (x + 1, y), columnar=True).chunks())
    assert all(k == "data" and isinstance(p, ColumnChunk)
               for k, p in items)
    assert [int(p.cols[1][0]) for _, p in items] == [0, 4]


class TestInterleave:
  def test_deterministic_round_robin(self):
    chunks = _chunks(4)
    ds = Dataset.interleave(
        [Dataset.from_chunks([chunks[0], chunks[1]]),
         Dataset.from_chunks([chunks[2], chunks[3]])], cycle=2)
    order = [int(p.cols[1][0]) for _, p in ds.chunks()]
    assert order == [0, 8, 4, 12]

  def test_throughput_mode_completes(self):
    chunks = _chunks(6)
    ds = Dataset.interleave(
        [Dataset.from_chunks(chunks[0:2]),
         Dataset.from_chunks(chunks[2:4]),
         Dataset.from_chunks(chunks[4:6])], cycle=3)
    vals = sorted(int(p.cols[1][0])
                  for _, p in ds.chunks(deterministic=False))
    assert vals == [0, 4, 8, 12, 16, 20]

  def test_cycle_activates_pending_sources(self):
    chunks = _chunks(4)
    ds = Dataset.interleave(
        [Dataset.from_chunks([c]) for c in chunks], cycle=2)
    order = [int(p.cols[1][0]) for _, p in ds.chunks()]
    assert sorted(order) == [0, 4, 8, 12]
    # the first two sources drain before the pending ones activate
    assert set(order[:2]) == {0, 4}

  def test_end_partition_rides_the_merge(self):
    chunks = _chunks(2)
    ds = Dataset.interleave(
        [Dataset.from_chunks([chunks[0], EndPartition()]),
         Dataset.from_chunks([chunks[1]])], cycle=2)
    kinds = [(k, type(p).__name__) for k, p in ds.chunks()]
    assert ("marker", "EndPartition") in kinds
    assert len([k for k, _ in kinds if k == "data"]) == 2

  def test_sources_must_be_pure(self):
    with pytest.raises(ValueError):
      Dataset.interleave(
          [Dataset.from_chunks(_chunks()).map(lambda r: r)], cycle=1)
    with pytest.raises(ValueError):
      Dataset.interleave([])

  def test_interleave_composes_with_batch(self):
    chunks = _chunks(4)
    ds = Dataset.interleave(
        [Dataset.from_chunks(chunks[0:2], columns=["x", "y"]),
         Dataset.from_chunks(chunks[2:4], columns=["x", "y"])],
        cycle=2).batch(16)
    got = list(ds.batches())
    assert sorted(_labels(got)) == list(range(16))


class TestFeedGraphParity:
  """The determinism contract against a REAL feed hub: the graph is
  batch-for-batch, byte-for-byte ``slab_batches``."""

  ROWS = 38   # 4 full (4x2)-slabs + a 6-row tail: tail split exercised

  def _fill(self, hub, with_marker=True):
    rows = [(np.random.RandomState(i).rand(4).astype("float32"), i)
            for i in range(self.ROWS)]
    chunks = [rows[i:i + 5] for i in range(0, len(rows), 5)]
    q = hub.get_queue("input")
    for i, c in enumerate(chunks):
      put_rows_chunk(q, c, timeout=5)
      if with_marker and i == 3:
        q.put(EndPartition())
    q.put(None)

  def _feed(self, hub, **kw):
    kw.setdefault("train_mode", True)
    return DataFeed(hub, input_mapping={"c0": "x", "c1": "y"},
                    pipeline_depth=0, **kw)

  def test_from_feed_slab_matches_slab_batches(self, hub):
    self._fill(hub)
    ref = list(slab_batches(self._feed(hub), 4, 2))
    h2 = feedhub.start(b"k", ["input", "output", "error"], mode="local")
    try:
      self._fill(h2)
      feed = self._feed(h2)
      got = list(Dataset.from_feed(feed).slab(4, 2).batches())
      assert feed.should_stop()
    finally:
      h2.shutdown()
    assert len(ref) == len(got)
    for a, b in zip(ref, got):
      assert type(a) is type(b)
      da = a.data if isinstance(a, Slab) else a
      db = b.data if isinstance(b, Slab) else b
      for k in da:
        assert da[k].dtype == db[k].dtype
        assert np.array_equal(da[k], db[k])

  def test_from_feed_batch_matches_feed_batches(self, hub):
    from tensorflowonspark_tpu.data.readers import feed_batches
    self._fill(hub)
    ref = list(feed_batches(self._feed(hub), 8))
    h2 = feedhub.start(b"k", ["input", "output", "error"], mode="local")
    try:
      self._fill(h2)
      got = list(Dataset.from_feed(self._feed(h2)).batch(8).batches())
    finally:
      h2.shutdown()
    assert len(ref) == len(got)
    for a, b in zip(ref, got):
      for k in a:
        assert np.array_equal(a[k], b[k])

  def test_inference_boundaries_match(self, hub):
    from tensorflowonspark_tpu.data.readers import feed_batches
    self._fill(hub)
    ref = list(feed_batches(self._feed(hub, train_mode=False), 8))
    h2 = feedhub.start(b"k", ["input", "output", "error"], mode="local")
    try:
      self._fill(h2)
      got = list(Dataset.from_feed(self._feed(h2, train_mode=False))
                 .batch(8).batches())
    finally:
      h2.shutdown()
    assert [len(b["x"]) for b in ref] == [len(b["x"]) for b in got]
    for a, b in zip(ref, got):
      assert np.array_equal(a["x"], b["x"])

  def test_from_feed_retires_the_feeds_own_pipeline(self, hub):
    self._fill(hub)
    feed = DataFeed(hub, input_mapping={"c0": "x", "c1": "y"},
                    pipeline_depth=2)
    feed._fetch(1.0)                      # starts the fixed prefetcher
    assert feed._pipeline is not None
    Dataset.from_feed(feed)
    assert feed._pipeline is None         # graph owns the channel now


def _tl_scale(v, i):
  return (v * 2.0).astype(np.float32), i


def _tl_shift(v, i):
  return (v + 1.0).astype(np.float32), i


class TestTrainLoopIntegration:
  @pytest.mark.parametrize("maps", [False, True])
  def test_graph_drives_fused_loop_bit_identical(self, hub, maps):
    """from_feed(...).slab(B, K) -> make_train_loop(unroll=K) produces
    the exact PR 9 trajectory (losses AND params), through a real hub,
    with the autotuner enabled — autotuning may change THROUGHPUT,
    never values. ``maps``: two columnar map stages ride the graph's
    worker pools, against the same maps applied to the rows before the
    fixed-depth path sees them."""
    import jax
    import optax
    from flax.training import train_state as ts
    from tensorflowonspark_tpu.parallel import mesh as mesh_lib
    from tensorflowonspark_tpu.parallel import sharding

    rng = np.random.RandomState(0)
    w_true = rng.rand(4, 2).astype("float32")
    params0 = {"w": np.asarray(rng.rand(4, 2).astype("float32"))}
    rows = []
    for i in range(38):
      x = rng.rand(4).astype("float32")
      rows.append((np.concatenate([x, x @ w_true]), i))
    chunks = [rows[i:i + 5] for i in range(0, len(rows), 5)]

    def mapped(c):
      if not maps:
        return c
      v, i = _tl_shift(*_tl_scale(np.stack([r[0] for r in c]),
                                  [r[1] for r in c]))
      return list(zip(v, i))

    def fill(h, pre=False):
      q = h.get_queue("input")
      for i, c in enumerate(chunks):
        put_rows_chunk(q, mapped(c) if pre else c, timeout=5)
        if i == 2:
          q.put(EndPartition())
      q.put(None)

    def loss_fn(params, batch):
      xy = batch["v"]
      pred = xy[:, :4] @ params["w"]
      return ((pred - xy[:, 4:]) ** 2).mean()

    mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=-1),
                               devices=jax.devices()[:1])

    def fresh_state():
      import jax.numpy as jnp
      return ts.TrainState.create(
          apply_fn=None, params=jax.tree.map(jnp.array, params0),
          tx=optax.adam(0.05))

    def run(items):
      loop = sharding.make_train_loop(loss_fn, mesh, unroll=4)
      state = fresh_state()
      losses = []
      for item in items:
        state, out = loop(state, item)
        losses.extend(np.asarray(out).reshape(-1).tolist())
      return losses, jax.tree.map(np.asarray, state.params)

    fill(hub, pre=True)
    feed = DataFeed(hub, input_mapping={"c0": "v", "c1": "i"},
                    pipeline_depth=0)
    # slab_batches yields {"v","i"}; the loop only consumes "v"
    ref_losses, ref_params = run(slab_batches(feed, 4, 4))

    h2 = feedhub.start(b"k", ["input", "output", "error"], mode="local")
    try:
      fill(h2)
      feed2 = DataFeed(h2, input_mapping={"c0": "v", "c1": "i"},
                       pipeline_depth=0)
      ds = Dataset.from_feed(feed2)
      if maps:
        ds = ds.map(_tl_scale, columnar=True).map(_tl_shift, columnar=True)
      ex = ds.slab(4, 4).start(deterministic=True, autotune=True)
      got_losses, got_params = run(ex.batches())
      stages = ex.stage_summary()
    finally:
      h2.shutdown()

    # the executor ran every declared stage as a live one
    assert set(stages) >= ({"src", "map0", "map1"} if maps else {"src"})
    assert all(d["workers"] >= 1 and d["depth"] >= 1
               for d in stages.values())

    assert got_losses == ref_losses
    for k in ref_params:
      assert np.array_equal(ref_params[k], got_params[k])
    assert ref_losses[-1] < ref_losses[0]     # it learned something


class TestExecutor:
  @pytest.mark.slow
  def test_autotuner_adds_worker_to_hot_stage_order_pinned(self,
                                                           monkeypatch):
    # Marked slow (tier-1 budget audit): the assertion that the tuner
    # OBSERVES a hot stage within the run is wall-clock-sampled and
    # flakes when the shared CI box is saturated; the autotuned graph's
    # determinism + parity stay tier-1-pinned via
    # test_graph_drives_fused_loop_bit_identical[True] (map stages, the
    # tuner live) and test_autotune_off_keeps_declared_plan. Runs in
    # `make test`.
    monkeypatch.setenv(datapipe.ENV_DATA_AUTOTUNE_INTERVAL, "0.05")
    chunks = [[(np.full(8, 16 * c + i, np.float32), 16 * c + i)
               for i in range(16)] for c in range(60)]

    def slow(x, y):
      t = x
      for _ in range(400):
        t = np.sqrt(t * t + 1.0)
      return t, y

    ds = (Dataset.from_chunks(chunks, columns=["x", "y"])
          .map(slow, columnar=True).batch(16))
    ex = ds.start(deterministic=True, autotune=True)
    got = _labels(list(ex.batches()))
    assert got == list(range(960))            # order survived the moves
    assert ex.stats["autotune_moves"] >= 1
    assert ex.stage_summary()["map0"]["workers"] >= 2
    ev = list(ex.autotune_events)
    assert ev and ev[0]["action"] in ("add_worker", "grow_buffer")
    assert "stage" in ev[0] and "t" in ev[0]

  def test_autotune_off_keeps_declared_plan(self, monkeypatch):
    monkeypatch.setenv(datapipe.ENV_DATA_AUTOTUNE_INTERVAL, "0.05")
    monkeypatch.setenv(datapipe.ENV_DATA_AUTOTUNE, "0")
    chunks = [[(np.full(8, 4 * c + i, np.float32), 4 * c + i)
               for i in range(4)] for c in range(30)]

    def slowish(x, y):
      t = x
      for _ in range(200):
        t = np.sqrt(t * t + 1.0)
      return t, y

    ds = (Dataset.from_chunks(chunks, columns=["x", "y"])
          .map(slowish, columnar=True).batch(8))
    ex = ds.start(deterministic=True)        # autotune resolves from env
    _ = list(ex.batches())
    assert ex.stats["autotune_moves"] == 0
    assert ex.stage_summary()["map0"]["workers"] == 1

  def test_worker_error_reraises_in_consumer(self):
    def boom(x, y):
      raise RuntimeError("map exploded")
    ds = (Dataset.from_chunks(_chunks(), columns=["x", "y"])
          .map(boom, columnar=True).batch(4))
    with pytest.raises(RuntimeError, match="map exploded"):
      list(ds.batches())

  def test_source_error_reraises_in_consumer(self):
    def bad_source():
      yield _chunks(1)[0]
      raise IOError("reader died")
    ds = Dataset.from_chunks(bad_source(), columns=["x", "y"]).batch(64)
    with pytest.raises(IOError, match="reader died"):
      list(ds.batches())

  def test_stats_snapshot_covers_nested_stage_dicts(self):
    ds = Dataset.from_chunks(_chunks(), columns=["x", "y"]).batch(4)
    ex = datapipe.GraphExecutor(ds)
    snap = ex.stats_snapshot()      # BEFORE start: full deltas visible
    ex.start()
    try:
      got = list(ex.batches())
      assert got
      d = snap.delta()
      assert d["batches"] == len(got)
      assert d["rows"] == 20
      assert d["stages"]["src"]["items"] >= 5
      assert d["stages"]["assemble"]["items"] >= 5
      # a second snapshot sees zero delta immediately
      assert ex.stats_snapshot().delta()["batches"] == 0
    finally:
      ex.stop()

  def test_buffer_waits_are_bounded(self):
    buf = datapipe._Buffer(capacity=1)
    assert buf.pipe_put("a", timeout=0.05)
    t0 = time.monotonic()
    assert not buf.pipe_put("b", timeout=0.1)     # full: bounded timeout
    assert time.monotonic() - t0 < 2.0
    assert buf.pipe_get(timeout=0.05) == "a"
    t0 = time.monotonic()
    assert buf.pipe_get(timeout=0.1) is datapipe._EMPTY
    assert time.monotonic() - t0 < 2.0
    buf.set_capacity(2)
    assert buf.pipe_put("c", timeout=0.05)
    assert buf.pipe_put("d", timeout=0.05)

  def test_nondeterministic_marker_barrier(self):
    """Throughput mode scrambles data order but markers never overtake
    earlier items: everything fed before the end-of-feed marker is
    delivered before the stream ends."""
    chunks = _chunks(12)
    ds = (Dataset.from_chunks(chunks, columns=["x", "y"])
          .map(lambda x, y: (x, y), columnar=True).batch(100))
    got = _labels(list(ds.batches(deterministic=False)))
    assert sorted(got) == list(range(48))

  def test_nondeterministic_data_never_overtakes_held_marker(self):
    """The barrier's OTHER direction, at the emitter seam: once an
    upstream has announced a marker seq (always before the marker can
    enter the stage's input buffer), later data from a fast worker must
    HOLD until the marker releases — otherwise next-partition rows leak
    into the previous partition's batch."""
    import threading
    buf = datapipe._Buffer(8)
    down = datapipe._OrderedEmitter(buf, deterministic=False)
    stop = threading.Event()
    stats = {"out_wait_s": 0.0}
    data = lambda tag: ("data", [tag])  # noqa: E731

    down.expect_marker(1)               # upstream announced: seq 1 is it
    # a fast worker finishes seq 2 (data AFTER the marker) first
    assert down.emit(2, [data("late")], stop, stats)
    assert len(buf) == 0                # held behind the in-flight marker
    # data BEFORE the marker still flushes ahead of it
    assert down.emit(0, [data("early")], stop, stats)
    assert len(buf) == 1
    # the marker arrives: everything releases in stream order
    assert down.emit(1, [("marker", EndPartition)], stop, stats)
    order = []
    while len(buf):
      order.append(buf.pipe_get(timeout=0.1)[1])
    assert order == [data("early"), ("marker", EndPartition), data("late")]
    assert not down._expected_markers   # expectation cleared on release

  def test_stop_idempotent_and_generator_close(self):
    ds = Dataset.from_chunks(_chunks(100, rows=8), columns=["x", "y"]) \
        .batch(8)
    ex = ds.start()
    gen = ex.batches()
    assert next(gen) is not None
    gen.close()                   # early consumer exit stops the executor
    ex.stop()
    ex.stop()


class TestObsWiring:
  @pytest.fixture()
  def registry(self):
    reg = obs_metrics.activate(obs_metrics.MetricsRegistry())
    yield reg
    obs_metrics.deactivate()

  def test_stage_gauges_and_counters_mirror(self, registry):
    got = list(Dataset.from_chunks(_chunks(8), columns=["x", "y"])
               .map(lambda x, y: (x, y), columnar=True)
               .batch(8).batches(autotune=True))
    snap = registry.snapshot()
    assert snap["feed.batches"]["value"] == len(got)
    assert snap["feed.rows"]["value"] == 32
    # per-stage busy gauges exist for the fetch/decode virtual stages
    # and every declared stage — the feed_stall detector's attribution
    # wire and obs_top's pipe[...] suffix. The executor mirrors a final
    # pass at stop(), so even a sub-interval run exports them.
    for name in ("feed.stage.fetch.busy_s", "feed.stage.decode.busy_s",
                 "feed.stage.map0.busy_s", "feed.stage.assemble.busy_s",
                 "feed.stage.map0.workers", "feed.stage.map0.depth"):
      assert name in snap, name

  def test_autotune_policy_moves_and_event_fanout(self, registry):
    """The control loop, driven with a fabricated delta (no wall-clock
    dependence): a hot parallelizable stage gains a worker, a hot
    stateful stage gets a deeper buffer, a cold pool shrinks — each
    move counted, ring-buffered, and emitted as a structured recorder
    event."""
    from tensorflowonspark_tpu.obs import spans as obs_spans
    rec = obs_spans.activate(obs_spans.SpanRecorder(capacity=128))
    try:
      ds = (Dataset.from_chunks([], columns=["x", "y"])
            .map(lambda x, y: (x, y), columnar=True).batch(8))
      ex = datapipe.GraphExecutor(ds, autotune=True)
      tuner = datapipe._Autotuner(ex)
      try:
        # hot map stage => add a worker
        move = tuner._decide(
            {"src": {"fetch_s": 0.1, "decode_s": 0.0},
             "map0": {"busy_s": 4.5},
             "assemble": {"busy_s": 0.01}}, dt=5.0)
        assert move["action"] == "add_worker" and move["stage"] == "map0"
        assert ex._stages[0].target == 2
        # hot stateful assemble => deepen ITS hand-off buffer
        move = tuner._decide(
            {"src": {"fetch_s": 0.1, "decode_s": 0.0},
             "map0": {"busy_s": 0.2},
             "assemble": {"busy_s": 4.8}}, dt=5.0)
        assert move["action"] == "grow_buffer"
        assert move["stage"] == "assemble"
        # cold map pool (grown above) donates its worker back
        move = tuner._decide(
            {"src": {"fetch_s": 0.1, "decode_s": 0.0},
             "map0": {"busy_s": 0.0},
             "assemble": {"busy_s": 0.2}}, dt=5.0)
        assert move["action"] == "remove_worker"
        assert move["stage"] == "map0"
        assert ex.stats["autotune_moves"] == 3
        assert len(ex.autotune_events) == 3
        assert registry.snapshot()["feed.autotune_moves"]["value"] == 3
        events = [s for s in rec.drain()
                  if s.get("name") == "feed.autotune"]
        assert [e["attrs"]["action"] for e in events] == \
            ["add_worker", "grow_buffer", "remove_worker"]
        assert all("stage" in e["attrs"] for e in events)
      finally:
        ex.stop()
    finally:
      obs_spans.deactivate()


def _pd_map(x, y):
  return (x[:, :2] * 2.0).astype(np.float32), y


def _pd_filter(x, y):
  return np.asarray(y) % 3 != 0


def _pd_int_map(x, y):
  # stays int32 with 16 distinct values: the mapped column is dict-able,
  # so the codec is priced on the pushed segment's real output
  return (x[:, :16] % 16).astype(np.int32), y


class TestPushdown:
  """Feeder-side transform pushdown (split_pushdown / FeederSegment):
  the pushable map/filter prefix applied FEEDER-side before the wire
  codec + the consumer remainder must be batch-for-batch bit-identical
  to the full consumer-side graph — pushdown moves computation, never
  order. Covered on both transports (hub queue and shm ring), with the
  end-of-feed tail and EndPartition boundaries included."""

  def _graph(self, src, fn=_pd_map):
    return (src.map(fn, columnar=True)
            .filter(_pd_filter, columnar=True))

  def test_split_carves_the_stateless_prefix(self):
    ds = (self._graph(Dataset.from_chunks([], columns=["x", "y"]))
          .shuffle(8, seed=1).batch(4))
    seg, rest = ds.split_pushdown()
    assert seg is not None
    assert [op[0] for op in seg.ops] == ["map", "filter"]
    assert [op[0] for op in rest._ops] == ["shuffle", "batch"]
    assert rest._columns == ds._columns
    assert rest._train_mode == ds._train_mode

  def test_split_stops_at_first_stateful_stage(self):
    ds = (Dataset.from_chunks([], columns=["x", "y"])
          .map(_pd_map, columnar=True).shuffle(8, seed=1)
          .filter(_pd_filter, columnar=True).batch(4))
    seg, rest = ds.split_pushdown()
    assert [op[0] for op in seg.ops] == ["map"]
    assert [op[0] for op in rest._ops] == ["shuffle", "filter", "batch"]

  def test_split_disabled_by_env(self, monkeypatch):
    monkeypatch.setenv(datapipe.ENV_FEED_PUSHDOWN, "0")
    ds = self._graph(Dataset.from_chunks([], columns=["x", "y"])).batch(4)
    seg, rest = ds.split_pushdown()
    assert seg is None and rest is ds

  def test_no_leading_prefix_no_split(self):
    ds = (Dataset.from_chunks([], columns=["x", "y"])
          .shuffle(8, seed=1).batch(4))
    seg, rest = ds.split_pushdown()
    assert seg is None and rest is ds

  def test_interleave_never_pushes(self):
    srcs = [Dataset.from_chunks([], columns=["x", "y"]) for _ in range(2)]
    ds = self._graph(Dataset.interleave(srcs)).batch(4)
    seg, rest = ds.split_pushdown()
    assert seg is None and rest is ds

  def test_prefetch_depths_remap_to_consumer_indices(self):
    ds = (Dataset.from_chunks([], columns=["x", "y"])
          .map(_pd_map, columnar=True).prefetch(6)
          .shuffle(8, seed=1).prefetch(3).batch(4))
    seg, rest = ds.split_pushdown()
    assert [op[0] for op in seg.ops] == ["map"]
    # the pushed stage's prefetch pads the consumer-side source buffer;
    # the shuffle's depth shifts with its new index
    assert rest._depths == {-1: 6, 0: 3}

  def test_segment_compile_matches_consumer_stages(self):
    chunks = _chunks(5, 4)
    seg, _ = (self._graph(Dataset.from_chunks(chunks, columns=["x", "y"]))
              .batch(6).split_pushdown())
    run = seg.compile()
    for rows in chunks:
      out = run(rows)
      assert isinstance(out, ColumnChunk)
      keep = [r for r in rows if r[1] % 3 != 0]
      assert out.n == len(keep)
      np.testing.assert_array_equal(
          out.cols[0], np.stack([(r[0][:2] * 2.0).astype(np.float32)
                                 for r in keep]))
      assert out.cols[1].tolist() == [r[1] for r in keep]

  def test_segment_filters_whole_chunk_to_none(self):
    seg = datapipe.FeederSegment(
        [("filter", lambda x, y: np.zeros(len(y), bool), True)])
    assert seg.compile()(_chunks(1, 4)[0]) is None

  def test_pending_template_cannot_start(self):
    tmpl = Dataset.pipeline().map(_pd_map, columnar=True).batch(4)
    with pytest.raises(ValueError, match="bind"):
      tmpl.batches()

  def test_bind_requires_pending_source(self, hub):
    feed = DataFeed(hub, input_mapping={"c0": "x", "c1": "y"},
                    pipeline_depth=0)
    with pytest.raises(ValueError, match="pipeline"):
      Dataset.from_chunks([]).bind(feed)

  ROWS = 38   # 7 full 5-row chunks + a 3-row tail; EndPartition mid-way

  def _rows(self):
    return [(np.random.RandomState(i).rand(4).astype("float32"), i)
            for i in range(self.ROWS)]

  def _fill_raw(self, q, chunks):
    for i, c in enumerate(chunks):
      put_rows_chunk(q, c, timeout=5)
      if i == 3:
        q.put(EndPartition())
    q.put(None)

  def _int_rows(self, n=400):
    """Rows the wire codec can shrink: low-cardinality int pixels
    (dict-able once mapped) and a monotone id (delta-able)."""
    idx = np.arange(n, dtype=np.int64)
    px = ((idx[:, None] * 2654435761 + np.arange(64)[None, :] * 40503)
          % 256).astype(np.int32)
    return [(px[i], int(i)) for i in range(n)]

  def _fill_pushed(self, q, chunks, segment, sizer=None, stats=None):
    from tensorflowonspark_tpu import node
    run = segment.compile()
    for i, c in enumerate(chunks):
      buf = list(c)
      while buf:   # a byte budget re-cuts the source chunk into envelopes
        n = sizer.rows if sizer is not None else len(buf)
        node._flush_chunk(q, buf[:n], run, sizer, 5, stats=stats)
        del buf[:n]
      if i == 3:
        q.put(EndPartition())
    q.put(None)

  def _batches(self, ds):
    out = []
    for b in ds.batches():
      out.append({k: np.asarray(v) for k, v in b.items()})
    return out

  def _assert_parity(self, ref, got):
    assert len(ref) == len(got)
    for a, b in zip(ref, got):
      assert set(a) == set(b)
      for k in a:
        assert a[k].dtype == b[k].dtype
        np.testing.assert_array_equal(a[k], b[k])

  @pytest.mark.parametrize("train_mode,wire", [
      (True, "raw"), (False, "raw"), (True, "compress"), (True, "adaptive")])
  def test_pushdown_parity_queue_transport(self, hub, monkeypatch,
                                           train_mode, wire):
    """The four legs of the wire plane against the raw consumer-side
    graph: pushdown alone (``raw``), pushdown + the per-column wire
    encodings (``compress``), and those + the envelope byte budget,
    which moves the chunk boundaries (``adaptive``). The wire plane
    moves computation and re-encodes bytes; it never changes a batch."""
    from tensorflowonspark_tpu import node
    from tensorflowonspark_tpu.control import chunkcodec
    if wire == "raw":
      rows, size, fn = self._rows(), 5, _pd_map
    else:
      rows, size, fn = self._int_rows(), 50, _pd_int_map
      monkeypatch.setenv(chunkcodec.ENV_FEED_WIRE_ENCODINGS, "")
    chunks = [rows[i:i + size] for i in range(0, len(rows), size)]
    self._fill_raw(hub.get_queue("input"), chunks)
    feed = DataFeed(hub, input_mapping={"c0": "x", "c1": "y"},
                    pipeline_depth=0, train_mode=train_mode)
    ref = self._batches(self._graph(Dataset.from_feed(feed), fn).batch(8))

    monkeypatch.delenv(chunkcodec.ENV_FEED_WIRE_ENCODINGS, raising=False)
    meta = {"feed_target_bytes": 512 if wire == "adaptive" else None}
    _, _, sizer = node._feed_plan(meta, size)
    picks = {}
    h2 = feedhub.start(b"k", ["input", "output", "error"], mode="local")
    try:
      tmpl = self._graph(Dataset.pipeline(), fn).batch(8)
      seg, rest = tmpl.split_pushdown()
      assert seg is not None
      self._fill_pushed(h2.get_queue("input"), chunks, seg, sizer, picks)
      feed2 = DataFeed(h2, input_mapping={"c0": "x", "c1": "y"},
                       pipeline_depth=0, train_mode=train_mode)
      got = self._batches(rest.bind(feed2))
    finally:
      h2.shutdown()
    self._assert_parity(ref, got)
    if wire != "raw":   # the codec engaged, and the budget moved the cuts
      assert any(n for enc, n in picks.items() if enc != "raw"), picks
      assert (sizer is None) == (wire == "compress")
      # two columns an envelope: the budget cut more envelopes than chunks
      assert sizer is None or sum(picks.values()) > 2 * len(chunks)

  def test_pushdown_parity_shm_ring_transport(self, hub):
    import uuid
    from tensorflowonspark_tpu.control import shmring
    rows = self._rows()
    chunks = [rows[i:i + 5] for i in range(0, len(rows), 5)]
    self._fill_raw(hub.get_queue("input"), chunks)
    feed = DataFeed(hub, input_mapping={"c0": "x", "c1": "y"},
                    pipeline_depth=0)
    ref = self._batches(self._graph(Dataset.from_feed(feed)).batch(8))

    h2 = feedhub.start(b"k", ["input", "output", "error"], mode="local")
    name = "tos_pd_%s" % uuid.uuid4().hex[:8]
    try:
      with shmring.ShmRing.create(name, capacity=1 << 20) as ring:
        h2.set("ring_name", name)
        from tensorflowonspark_tpu import node
        prod = node.input_channel(h2)   # resolves the advertised ring
        assert isinstance(prod, shmring.RingQueueAdapter)
        tmpl = self._graph(Dataset.pipeline()).batch(8)
        seg, rest = tmpl.split_pushdown()
        self._fill_pushed(prod, chunks, seg)
        feed2 = DataFeed(h2, input_mapping={"c0": "x", "c1": "y"},
                         pipeline_depth=0)
        got = self._batches(rest.bind(feed2))
        del ring
    finally:
      h2.shutdown()
    self._assert_parity(ref, got)
