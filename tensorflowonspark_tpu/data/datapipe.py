"""Autotuned declarative input pipeline over the columnar chunk plane.

The feed plane so far is a dumb conveyor (the reference's
``InputMode.SPARK`` shape): ``datafeed._FetchPipeline`` is ONE
fixed-depth fetch thread, and every map/shuffle/batch decision lives in
user code between ``next_batch_arrays`` and the jitted step. The tf.data
paper (PAPERS.md, arXiv 2101.12127) shows the winning design — a lazy,
declarative graph of composable transforms whose per-stage parallelism
and buffer depths are *autotuned* online — and this module is that
design at :class:`~tensorflowonspark_tpu.control.chunkcodec.ColumnChunk`
granularity:

- :class:`Dataset` is the lazy graph: ``from_feed(feed)`` /
  ``from_chunks(...)`` sources (plus ``Dataset.interleave([...])`` for
  parallel reads across hubs/files) composed with ``.map(fn)``,
  ``.filter(pred)``, ``.shuffle(buffer_rows)``, ``.batch(B)`` /
  ``.slab(B, K)`` and ``.prefetch(depth)``. Nothing runs until
  ``.batches()`` / ``.start()``.
- Transforms have a COLUMNAR fast path (``columnar=True``: the fn sees
  whole column arrays, vectorized over the chunk, no per-row Python
  loop) and a row fallback (the fn sees one row at a time; results are
  re-columnarized when homogeneous so the downstream stages stay on the
  fast path).
- :class:`GraphExecutor` is ``_FetchPipeline`` grown into a multi-stage
  executor: per-stage bounded hand-off buffers (:class:`_Buffer`, whose
  ``pipe_get``/``pipe_put`` verbs are in the analyzer's TOS001
  bounded-wait set — every wait is timeout-bounded) and worker pools,
  with an online :class:`_Autotuner` that reallocates stage parallelism
  and buffer depths from the live per-stage gauges (the same
  dominant-stage attribution the obs plane's ``feed_stall`` detector
  uses as its error signal — docs/OBSERVABILITY.md).
- ``deterministic=True`` (the default) pins element order end to end —
  per-stage sequence-ordered emit, round-robin interleave — so
  ``from_feed(feed).slab(B, K)`` yields the exact batches
  ``data.readers.slab_batches(feed, B, K)`` yields and the fused train
  loop's bit-identical-trajectory contract composes with the graph.
  ``deterministic=False`` is the throughput mode: map/filter outputs
  emit as they finish and interleave pulls whichever source is ready
  (markers still act as order barriers, so end-of-feed /
  ``EndPartition`` semantics survive).

Marker semantics are IDENTICAL to ``feed_batches``/``slab_batches``:
end-of-feed flushes a partial final batch and ends the stream;
``EndPartition`` is skipped in train mode and ends the
batch/slab-stretch early in inference mode (short stretches split into
the same per-step batches ``slab_batches`` would yield — what makes the
fused trajectory bit-identical through the graph).

Env knobs (registry: TOS008; see docs/API.md §datapipe):

==========================  ==================================================
``TOS_DATA_AUTOTUNE``       online autotuner on/off (default on; the gauge
                            mirror keeps running either way)
``TOS_DATA_AUTOTUNE_INTERVAL``  seconds between autotune passes (default 0.5)
``TOS_DATA_MAX_WORKERS``    per-stage worker cap (default 4)
``TOS_DATA_BUFFER_CAP``     per-stage hand-off buffer depth cap (default 32)
==========================  ==================================================
"""

import collections
import logging
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

from tensorflowonspark_tpu.control import chunkcodec
from tensorflowonspark_tpu.control.marker import Marker
from tensorflowonspark_tpu.obs import metrics as obs_metrics
from tensorflowonspark_tpu.obs import spans as obs_spans

logger = logging.getLogger(__name__)

#: online autotuner master switch (default on; ``0`` keeps the executor
#: at its declared worker/depth plan) — env registry: TOS008
ENV_DATA_AUTOTUNE = "TOS_DATA_AUTOTUNE"
#: seconds between autotune passes (also the stage-gauge mirror cadence)
ENV_DATA_AUTOTUNE_INTERVAL = "TOS_DATA_AUTOTUNE_INTERVAL"
#: per-stage worker-pool cap the autotuner may grow to (TOS008)
ENV_DATA_MAX_WORKERS = "TOS_DATA_MAX_WORKERS"
#: per-stage hand-off buffer depth cap the autotuner may grow to (TOS008)
ENV_DATA_BUFFER_CAP = "TOS_DATA_BUFFER_CAP"
#: feeder-side transform pushdown master switch (default on; ``0`` keeps
#: every stage consumer-side — :meth:`Dataset.split_pushdown` then always
#: returns the whole graph as the consumer segment) — env registry: TOS008
ENV_FEED_PUSHDOWN = "TOS_FEED_PUSHDOWN"

_DEFAULT_INTERVAL = 0.5
_DEFAULT_MAX_WORKERS = 4
_DEFAULT_BUFFER_CAP = 32
#: initial hand-off depth per stage (the `_FetchPipeline` default)
_DEFAULT_DEPTH = 2

#: bound on every blocking wait inside the executor (TOS001: a wedged
#: consumer or producer must never pin a worker past its stop check)
_POLL = 0.25

#: a stage must run at/above this busy fraction (per worker) before the
#: autotuner calls it dominant and spends a move on it
_HOT_UTIL = 0.5
#: a stage below this busy fraction per worker donates a worker back
_COLD_UTIL = 0.05

_EMPTY = object()   # pipe_get timeout sentinel (None is a real marker)


def _env_float(name: str, default: float) -> float:
  try:
    return float(os.environ.get(name, default))
  except ValueError:
    return default


def _env_int(name: str, default: int) -> int:
  try:
    return int(os.environ.get(name, default))
  except ValueError:
    return default


# -- chunk helpers ------------------------------------------------------------


def _rows_to_chunk(rows: List) -> Optional[chunkcodec.ColumnChunk]:
  """Best-effort columnarization of a row list (no codec round-trip).

  The in-process analog of ``chunkcodec.encode``'s eligibility rules:
  homogeneous ndarray columns stack, exact python bool/int/float scalar
  columns pack (dtype kind must round-trip the python type — the codec's
  int-beyond-int64 rule). Returns None when the rows are heterogeneous —
  the caller keeps the row representation and downstream stages use
  their row fallbacks.
  """
  import numpy as np
  if not rows:
    return None
  first = rows[0]
  tuples = isinstance(first, tuple)
  if tuples:
    width = len(first)
    if width == 0 or not all(isinstance(r, tuple) and len(r) == width
                             for r in rows):
      return None
    columns = [[r[j] for r in rows] for j in range(width)]
  else:
    if isinstance(first, (Marker,)) or first is None:
      return None
    columns = [rows]
  cols, scalar = [], []
  for values in columns:
    v0 = values[0]
    if isinstance(v0, np.ndarray):
      dtype, shape = v0.dtype, v0.shape
      if dtype == object or not all(
          isinstance(v, np.ndarray) and v.dtype == dtype and v.shape == shape
          for v in values):
        return None
      cols.append(np.stack(values))
      scalar.append(0)
      continue
    kind = type(v0)
    if kind not in (bool, int, float) or \
        not all(type(v) is kind for v in values):
      return None
    try:
      arr = np.asarray(values)
    except OverflowError:
      return None
    if arr.dtype.kind != {bool: "b", int: "i", float: "f"}[kind]:
      return None
    cols.append(arr)
    scalar.append(1)
  return chunkcodec.ColumnChunk(cols, scalar, tuples, len(rows))


def _chunk_from_cols(cols: Sequence, like: chunkcodec.ColumnChunk
                     ) -> chunkcodec.ColumnChunk:
  """Wrap transform output columns as a ColumnChunk (schema may differ
  from ``like``; scalar flags carry over positionally where they can)."""
  import numpy as np
  cols = [np.asarray(c) for c in cols]
  n = len(cols[0])
  if any(len(c) != n for c in cols):
    raise ValueError("columnar transform returned columns of unequal "
                     "length: %r" % ([len(c) for c in cols],))
  if len(cols) == len(like.cols):
    scalar = list(like.scalar)
  else:
    scalar = [1 if c.ndim == 1 else 0 for c in cols]
  tuples = like.tuples or len(cols) > 1
  return chunkcodec.ColumnChunk(cols, scalar, tuples, n)


def _split_inline_markers(item) -> List:
  """Expand a legacy row-list payload carrying INLINE markers (raw
  ``put_many`` streams — chunk-boundary envelopes ship markers alone)
  into marker-free segments with the markers as standalone items, in
  stream order."""
  kind, payload = item
  if kind != "data" or not isinstance(payload, list) or not any(
      r is None or isinstance(r, Marker) for r in payload):
    return [item]
  out: List = []
  seg: List = []
  for r in payload:
    if r is None or isinstance(r, Marker):
      if seg:
        chunk = _rows_to_chunk(seg)
        out.append(("data", chunk if chunk is not None else seg))
        seg = []
      out.append(("marker", r))
      if r is None:
        return out      # end-of-feed: nothing rides behind it
    else:
      seg.append(r)
  if seg:
    chunk = _rows_to_chunk(seg)
    out.append(("data", chunk if chunk is not None else seg))
  return out


def _normalize_source_item(obj):
  """Coerce one ``from_chunks`` element to the wire union
  (``("data", ColumnChunk|rows)`` / ``("marker", m)``)."""
  if obj is None or isinstance(obj, Marker):
    return ("marker", obj)
  if isinstance(obj, chunkcodec.ColumnChunk):
    return ("data", obj)
  if isinstance(obj, tuple) and len(obj) == 2 and obj[0] in ("data", "marker"):
    return obj
  if isinstance(obj, list):
    chunk = _rows_to_chunk(obj)
    return ("data", chunk if chunk is not None else obj)
  raise TypeError("from_chunks elements must be ColumnChunk, row list, "
                  "Marker or None (end-of-feed); got %r" % (type(obj),))


# -- bounded hand-off buffer --------------------------------------------------


class _Buffer(object):
  """Depth-bounded stage hand-off with a RESIZABLE capacity.

  ``queue.Queue``'s maxsize is fixed at construction; the autotuner
  needs to deepen a starved stage's buffer online, so this is a small
  condition-variable deque with a mutable ``capacity``. ``pipe_put`` /
  ``pipe_get`` are in the analyzer's TOS001 bounded-wait verb set:
  every call sites an explicit ``timeout``.
  """

  def __init__(self, capacity: int):
    self._cond = threading.Condition()
    self._items: collections.deque = collections.deque()
    self._capacity = max(1, int(capacity))

  @property
  def capacity(self) -> int:
    return self._capacity

  def set_capacity(self, n: int) -> None:
    with self._cond:
      self._capacity = max(1, int(n))
      self._cond.notify_all()

  def __len__(self) -> int:
    with self._cond:
      return len(self._items)

  def pipe_put(self, item, timeout: float) -> bool:
    """Append ``item`` within ``timeout`` seconds; False on timeout."""
    deadline = time.monotonic() + timeout
    with self._cond:
      while len(self._items) >= self._capacity:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
          return False
        self._cond.wait(timeout=min(remaining, _POLL))
      self._items.append(item)
      self._cond.notify_all()
      return True

  def pipe_get(self, timeout: float):
    """Pop the oldest item within ``timeout`` seconds; ``_EMPTY`` on
    timeout (None is a real payload: the end-of-feed marker)."""
    deadline = time.monotonic() + timeout
    with self._cond:
      while not self._items:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
          return _EMPTY
        self._cond.wait(timeout=min(remaining, _POLL))
      item = self._items.popleft()
      self._cond.notify_all()
      return item


class _OrderedEmitter(object):
  """Order-restoring boundary between a stage's worker pool and the next
  stage's buffer.

  Workers finish out of order (that is the point of the pool); the
  emitter re-serializes. ``deterministic=True``: every item is released
  in input-sequence order, so the graph's element order is a pure
  function of the source order. ``deterministic=False``: data items are
  released the moment their worker finishes (throughput mode), but
  MARKERS are order barriers both ways: a marker waits for every
  earlier item, and data behind an in-flight marker (announced by the
  upstream emitter via :meth:`expect_marker` before the marker enters
  the buffer, so there is no pull-race window) waits for the marker —
  end-of-feed and ``EndPartition`` keep their stream positions either
  way. The holding map is bounded by the stage's worker count (workers
  pull FIFO, so at most ``workers`` sequences are in flight).
  """

  def __init__(self, out: _Buffer, deterministic: bool):
    self._out = out
    self._det = deterministic
    self._lock = threading.Lock()
    self._next = 0          # next input seq to release
    self._held: Dict[int, List] = {}
    self._out_seq = 0
    #: the NEXT stage's emitter (None for the consumer-facing tail);
    #: marker seqs are announced to it at push time so its throughput-
    #: mode fast path can't let later data overtake an in-flight marker
    self.downstream: Optional["_OrderedEmitter"] = None
    self._expected_markers: set = set()   # announced, not yet released

  def expect_marker(self, seq: int) -> None:
    """Upstream announces: input ``seq`` is a marker (called BEFORE the
    marker enters this stage's input buffer, so the barrier is in place
    by the time any later data item can possibly reach :meth:`emit`)."""
    with self._lock:
      self._expected_markers.add(seq)

  def _push(self, outputs, stop: threading.Event, stats: Dict) -> bool:
    for item in outputs:
      seq = self._out_seq
      self._out_seq += 1
      if self.downstream is not None and self._is_marker(item):
        self.downstream.expect_marker(seq)
      t0 = time.perf_counter()
      while True:
        if self._out.pipe_put((seq, item), timeout=_POLL):
          break
        if stop.is_set():
          return False
      stats["out_wait_s"] += time.perf_counter() - t0
    return True

  @staticmethod
  def _is_marker(item) -> bool:
    return item[0] in ("marker", "end")

  def emit(self, seq: int, outputs: List, stop: threading.Event,
           stats: Dict) -> bool:
    """Hand one input sequence's outputs to the next stage. Returns
    False when the executor stopped mid-push."""
    with self._lock:
      if not self._det and not any(self._is_marker(i) for i in outputs) \
          and (not self._expected_markers
               or seq < min(self._expected_markers)):
        # throughput mode: data flushes now (no in-flight marker below
        # it — markers are order barriers); the seq is marked done so
        # held markers behind it can advance
        if seq == self._next or seq in self._held:
          pass    # in-order anyway (or duplicate): fall through to held
        else:
          if not self._push(outputs, stop, stats):
            return False
          self._held[seq] = []
          return self._advance(stop, stats)
      self._held[seq] = outputs
      return self._advance(stop, stats)

  def _advance(self, stop: threading.Event, stats: Dict) -> bool:
    while self._next in self._held:
      outputs = self._held.pop(self._next)
      self._expected_markers.discard(self._next)
      self._next += 1
      if outputs and not self._push(outputs, stop, stats):
        return False
    return True


# -- stage transform bodies ---------------------------------------------------


def _make_map(fn: Callable, columnar: bool) -> Callable:
  """A map stage body: item -> [item]. Markers pass through untouched."""

  def _apply(item):
    kind, payload = item
    if kind != "data":
      return [item]
    if isinstance(payload, chunkcodec.ColumnChunk):
      if columnar:
        out = fn(*payload.cols)
        cols = list(out) if isinstance(out, (tuple, list)) else [out]
        return [("data", _chunk_from_cols(cols, payload))]
      rows = [fn(r) for r in payload.rows()]
    else:
      if columnar:
        chunk = _rows_to_chunk(payload)
        if chunk is None:
          raise TypeError(
              "columnar map received a heterogeneous row chunk it cannot "
              "columnarize; use map(fn, columnar=False) for this stream")
        out = fn(*chunk.cols)
        cols = list(out) if isinstance(out, (tuple, list)) else [out]
        return [("data", _chunk_from_cols(cols, chunk))]
      rows = [fn(r) for r in payload]
    chunk = _rows_to_chunk(rows)
    return [("data", chunk if chunk is not None else rows)]

  return _apply


def _make_filter(pred: Callable, columnar: bool) -> Callable:
  """A filter stage body: item -> [item] (or [] when nothing survives)."""
  import numpy as np

  def _apply(item):
    kind, payload = item
    if kind != "data":
      return [item]
    if isinstance(payload, chunkcodec.ColumnChunk):
      if columnar:
        mask = np.asarray(pred(*payload.cols), dtype=bool).reshape(-1)
        if mask.shape[0] != payload.n:
          raise ValueError("columnar filter mask has %d entries for a "
                           "%d-row chunk" % (mask.shape[0], payload.n))
      else:
        mask = np.fromiter((bool(pred(r)) for r in payload.rows()),
                           dtype=bool, count=payload.n)
      if mask.all():
        return [item]
      if not mask.any():
        return []
      cols = [c[mask] for c in payload.cols]
      return [("data", chunkcodec.ColumnChunk(
          cols, list(payload.scalar), payload.tuples, int(mask.sum())))]
    rows = payload
    if columnar:
      chunk = _rows_to_chunk(rows)
      if chunk is None:
        raise TypeError(
            "columnar filter received a heterogeneous row chunk it cannot "
            "columnarize; use filter(pred, columnar=False)")
      return _apply(("data", chunk))
    kept = [r for r in rows if pred(r)]
    if not kept:
      return []
    chunk = _rows_to_chunk(kept)
    return [("data", chunk if chunk is not None else kept)]

  return _apply


class FeederSegment(object):
  """The pushable prefix of a :class:`Dataset` graph, run FEEDER-side.

  Holds the leading stateless ``map``/``filter`` ops split off by
  :meth:`Dataset.split_pushdown`. The segment travels to feeder tasks via
  cluster_meta (cloudpickled with the task closure, like the user fns)
  and executes inside the feeder BEFORE ``node.put_rows_chunk`` encodes —
  a filtered row never touches the codec, a projecting map shrinks
  columns before the wire.

  Pushdown moves COMPUTATION, never ORDER: the ops are applied to each
  chunk in stream position by the same stage bodies the consumer-side
  executor would run (``_make_map``/``_make_filter``), so
  ``deterministic=True`` and the fused-loop bit-identical-trajectory
  contract hold unchanged. Markers never enter a segment — they ride
  alone as chunk-boundary envelopes outside ``put_rows_chunk``.
  """

  __slots__ = ("ops",)

  def __init__(self, ops: List):
    self.ops = list(ops)

  def compile(self) -> Callable:
    """Build the feeder-side runner: ``rows -> ColumnChunk | rows | None``
    (None when the segment filters the whole chunk away). Built once per
    feeder task; the bodies are exactly the consumer-side stage bodies."""
    bodies = [_make_map(fn, columnar) if kind == "map"
              else _make_filter(fn, columnar)
              for kind, fn, columnar in self.ops]

    def _run(rows):
      chunk = _rows_to_chunk(rows)
      items = [("data", chunk if chunk is not None else rows)]
      for body in bodies:
        out = []
        for item in items:
          out.extend(body(item))
        items = out
        if not items:
          return None
      # map/filter bodies are 1 -> <=1, so one item survives at most
      return items[0][1]

    return _run

  def __repr__(self):
    return "FeederSegment(%s)" % ",".join(op[0] for op in self.ops)


class _ShuffleState(object):
  """Streaming row-granular shuffle at COLUMN granularity.

  Holds up to ``buffer_rows`` rows; once the buffer overflows, the
  overflow count is drawn uniformly (vectorized gather — one
  ``np.take`` per column, no per-row loop) and emitted as a fresh
  chunk. Markers flush the whole buffer shuffled first, so rows never
  cross an ``EndPartition`` / end-of-feed boundary. Deterministic per
  ``seed`` + arrival order. Heterogeneous row chunks (and schema
  changes) flush and fall back to row-list shuffling. Stateful —
  single-worker by construction (the planner pins it).
  """

  def __init__(self, buffer_rows: int, seed: int = 0):
    import numpy as np
    self._buffer_rows = max(1, int(buffer_rows))
    self._rng = np.random.RandomState(seed)
    self._cols = None         # list of per-column array-piece lists
    self._sig = None
    self._scalar = None
    self._tuples = False
    self._n = 0
    self._rows: List = []     # heterogeneous fallback buffer

  def _sig_of(self, chunk):
    return (len(chunk.cols),
            tuple((a.dtype.str, a.shape[1:]) for a in chunk.cols))

  def _flush_all(self) -> List:
    import numpy as np
    out = []
    if self._n:
      cols = [np.concatenate(p) for p in self._cols]
      perm = self._rng.permutation(self._n)
      cols = [c[perm] for c in cols]
      out.append(("data", chunkcodec.ColumnChunk(
          cols, list(self._scalar), self._tuples, self._n)))
      self._cols, self._sig, self._n = None, None, 0
    if self._rows:
      rows = list(self._rows)
      self._rng.shuffle(rows)
      out.append(("data", rows))
      self._rows = []
    return out

  def _emit_overflow(self) -> List:
    import numpy as np
    out = []
    while self._n > self._buffer_rows:
      take = self._n - self._buffer_rows
      cols = [np.concatenate(p) for p in self._cols]
      idx = self._rng.permutation(self._n)
      sent, kept = idx[:take], idx[take:]
      out.append(("data", chunkcodec.ColumnChunk(
          [c[sent] for c in cols], list(self._scalar), self._tuples, take)))
      self._cols = [[c[kept]] for c in cols]
      self._n = len(kept)
    return out

  def feed(self, item) -> List:
    kind, payload = item
    if kind != "data":
      return self._flush_all() + [item]
    if not isinstance(payload, chunkcodec.ColumnChunk):
      chunk = _rows_to_chunk(payload)
      if chunk is None:
        # heterogeneous rows: flush the columnar buffer, buffer rows
        out = self._flush_all() if self._n else []
        self._rows.extend(payload)
        if len(self._rows) > self._buffer_rows:
          rows = list(self._rows)
          self._rng.shuffle(rows)
          take = len(rows) - self._buffer_rows
          out.append(("data", rows[:take]))
          self._rows = rows[take:]
        return out
      payload = chunk
    out = []
    sig = self._sig_of(payload)
    if self._rows or (self._sig is not None and sig != self._sig):
      out.extend(self._flush_all())
    if self._sig is None or self._n == 0:
      self._sig = sig
      self._scalar = list(payload.scalar)
      self._tuples = payload.tuples
      self._cols = [[] for _ in payload.cols]
      self._n = 0
    for pieces, col in zip(self._cols, payload.cols):
      pieces.append(col)
    self._n += payload.n
    out.extend(self._emit_overflow())
    return out


class _AssembleState(object):
  """The terminal batch/slab assembly stage — ``_assemble_columns`` +
  ``slab_batches`` semantics reproduced over the in-executor stream.

  Plans rows across chunk boundaries and commits one output per
  ``batch_size`` (or ``batch_size*unroll`` for slabs): each output
  column is ONE ``np.concatenate`` over chunk slices (the hand-off
  copy, exactly the DataFeed fast path). Markers keep their row-path
  semantics: end-of-feed flushes the partial tail and ends the stream;
  ``EndPartition`` is skipped in train mode and ends the stretch in
  inference mode. A short SLAB stretch splits into the same per-step
  batches ``slab_batches`` yields (full ones first, short remainder
  last) — the bit-identical-trajectory contract. Stateful —
  single-worker by construction.
  """

  def __init__(self, batch_size: int, unroll: int = 1, dtype=None,
               columns: Optional[List[str]] = None, train_mode: bool = True):
    self.batch_size = int(batch_size)
    self.unroll = max(1, int(unroll))
    self.dtype = dtype
    self.columns = columns
    self.train_mode = train_mode
    self._plan: List = []      # (ColumnChunk, start, stop) in plan order
    self._rows: List = []      # row-mode fallback for the current stretch
    self._sig = None
    self._have = 0

  @property
  def _want(self) -> int:
    return self.batch_size * self.unroll

  def _demote_to_rows(self) -> None:
    rows = []
    for cc, a, b in self._plan:
      rows.extend(cc.rows(a)[:b - a])
    self._plan, self._sig = [], None
    self._rows = rows + self._rows

  def _emit_columns(self, arrays: List, n: int):
    """Shape one flushed stretch into the output payload(s)."""
    out = []
    if self.unroll > 1 and n == self._want:
      from tensorflowonspark_tpu.data.readers import Slab
      stacked = [a.reshape((self.unroll, self.batch_size) + a.shape[1:])
                 for a in arrays]
      if self.columns is not None:
        out.append(("batch", Slab(dict(zip(self.columns, stacked)))))
      elif len(stacked) == 1:
        out.append(("batch", Slab(stacked[0])))
      else:
        out.append(("batch", Slab(tuple(stacked))))
      return out
    # plain batches — and the short-slab tail split (full per-step
    # batches first, short remainder last: slab_batches order)
    for i in range(0, n, self.batch_size):
      part = [a[i:i + self.batch_size] for a in arrays]
      if self.columns is not None:
        out.append(("batch", dict(zip(self.columns, part))))
      elif len(part) == 1:
        out.append(("batch", part[0]))
      else:
        out.append(("batch", tuple(part)))
    return out

  def _flush(self) -> List:
    import numpy as np
    if self._rows:
      # row-mode stretch: stack per column (same values the columnar
      # concatenate yields for homogeneous rows)
      rows = self._rows
      self._rows = []
      if isinstance(rows[0], tuple):
        ncols = len(rows[0])
        arrays = [np.asarray([r[j] for r in rows]) for j in range(ncols)]
      else:
        arrays = [np.asarray(rows)]
    elif self._plan:
      ncols = len(self._plan[0][0].cols)
      if self.columns is not None:
        ncols = min(ncols, len(self.columns))
      arrays = []
      for j in range(ncols):
        pieces = [cc.cols[j][a:b] for cc, a, b in self._plan]
        arrays.append(np.concatenate(pieces)
                      if len(pieces) > 1 else np.asarray(pieces[0]))
      self._plan, self._sig = [], None
    else:
      return []
    if self.dtype is not None:
      dt = np.dtype(self.dtype)
      arrays = [a if a.dtype == dt else a.astype(dt) for a in arrays]
    n = len(arrays[0])
    self._have = 0
    return self._emit_columns(arrays, n)

  def feed(self, item) -> List:
    kind, payload = item
    if kind == "marker":
      if payload is None:                  # end-of-feed
        return self._flush() + [("end", None)]
      if self.train_mode:
        return []                          # EndPartition skipped in train
      return self._flush()                 # inference: stretch ends here
    # data
    if isinstance(payload, chunkcodec.ColumnChunk):
      sig = (len(payload.cols),
             tuple((a.dtype.str, a.shape[1:]) for a in payload.cols))
      if self._rows or (self._sig is not None and sig != self._sig):
        self._demote_to_rows()
        self._rows.extend(payload.rows())
        self._have += payload.n
      else:
        self._sig = sig
        self._plan.append((payload, 0, payload.n))
        self._have += payload.n
    else:
      if self._plan:
        self._demote_to_rows()
      self._rows.extend(payload)
      self._have += len(payload)
    out = []
    while self._have >= self._want:
      out.extend(self._take_exact(self._want))
    return out

  def _take_exact(self, want: int) -> List:
    """Split off exactly ``want`` planned rows and flush them."""
    if self._rows:
      head, self._rows = self._rows[:want], self._rows[want:]
      rest_have = self._have - want
      saved_rows, self._rows = self._rows, head
      self._have = want
      out = self._flush()
      self._rows = saved_rows
      self._have = rest_have
      return out
    taken, remaining = [], []
    left = want
    for cc, a, b in self._plan:
      if left <= 0:
        remaining.append((cc, a, b))
        continue
      take = min(left, b - a)
      taken.append((cc, a, a + take))
      left -= take
      if a + take < b:
        remaining.append((cc, a + take, b))
    saved_plan, saved_sig = remaining, self._sig
    rest_have = self._have - want
    self._plan, self._have = taken, want
    out = self._flush()
    self._plan, self._sig = saved_plan, saved_sig
    self._have = rest_have
    return out


# -- the executor -------------------------------------------------------------


class _StageRuntime(object):
  """One executor stage: a worker pool draining an input buffer through
  the transform body into an order-restoring emitter."""

  def __init__(self, name: str, body, parallelizable: bool,
               inbuf: Optional[_Buffer], emitter: _OrderedEmitter,
               stop: threading.Event):
    self.name = name
    self.body = body                      # item -> [item]
    self.parallelizable = parallelizable
    self.inbuf = inbuf
    self.emitter = emitter
    self._stop = stop
    self.target = 1
    self.active = 0          # live workers (a retiring worker decrements)
    self._spawned = 0
    self.threads: List[threading.Thread] = []
    self._lock = threading.Lock()
    # monotonic counters only (snapshot-subtract safe); worker threads
    # read-modify-write these, so readers must go through snapshot_stats
    self.stats = {"busy_s": 0.0, "items": 0, "in_wait_s": 0.0,
                  "out_wait_s": 0.0}

  @property
  def workers(self) -> int:
    return self.target

  def should_retire(self) -> bool:
    """Called by a worker each loop: True exactly once per shrink (the
    caller retires; identity-by-index breaks after shrink+grow cycles,
    a live-count handshake does not)."""
    with self._lock:
      if self.active > self.target:
        self.active -= 1
        return True
      return False

  def spawn(self, executor) -> None:
    with self._lock:
      if self.active >= self.target:
        return
      self.active += 1
      idx = self._spawned
      self._spawned += 1
      # retired workers stay in the list until the next spawn: prune
      # here so grow/shrink oscillation can't accumulate dead Threads
      self.threads = [x for x in self.threads if x.is_alive()]
      t = threading.Thread(target=executor._stage_worker, args=(self, idx),
                           daemon=True,
                           name="tos-pipe-%s-%d" % (self.name, idx))
      self.threads.append(t)
    t.start()

  def grow(self, executor) -> None:
    with self._lock:
      self.target += 1
    self.spawn(executor)

  def shrink(self) -> None:
    with self._lock:
      if self.target > 1:
        self.target -= 1


class GraphExecutor(object):
  """``_FetchPipeline`` grown into a multi-stage pipeline executor.

  Stages hand off through bounded :class:`_Buffer`\\ s; each transform
  stage owns a worker pool whose size (and whose buffer depth) the
  :class:`_Autotuner` reallocates online from the live per-stage
  gauges. Every blocking wait is timeout-bounded (TOS001); a worker
  error is forwarded and re-raised in the consumer; the source thread
  retires itself at end-of-feed. ``stats`` is a live dict mutated by
  the workers — read it through ``stats_snapshot()`` (the PR 4
  snapshot-subtract rule), never by zeroing or raw copies.
  """

  def __init__(self, plan: "Dataset", deterministic: bool = True,
               autotune: Optional[bool] = None):
    self._plan = plan
    self._det = bool(deterministic)
    if autotune is None:
      autotune = os.environ.get(ENV_DATA_AUTOTUNE, "1") not in ("0",)
    self._autotune = bool(autotune)
    self._max_workers = max(1, _env_int(ENV_DATA_MAX_WORKERS,
                                        _DEFAULT_MAX_WORKERS))
    self._buffer_cap = max(1, _env_int(ENV_DATA_BUFFER_CAP,
                                       _DEFAULT_BUFFER_CAP))
    self._stop_evt = threading.Event()
    self._error: Optional[BaseException] = None
    self._stages: List[_StageRuntime] = []
    self._buffers: List[_Buffer] = []
    self._source_threads: List[threading.Thread] = []
    self._tuner: Optional["_Autotuner"] = None
    self.autotune_events: collections.deque = collections.deque(maxlen=256)
    #: live executor-level stats; ``stages`` nests the per-stage dicts
    #: (obs.metrics.snapshot_stats recurses into them)
    self.stats: Dict[str, Any] = {"batches": 0, "rows": 0,
                                  "autotune_moves": 0, "stages": {}}
    # obs seam (docs/OBSERVABILITY.md): cached once, None when off
    self._rec = obs_spans.active()
    reg = obs_metrics.active()
    self._obs_m = None if reg is None else {
        "batches": reg.counter("feed.batches"),
        "rows": reg.counter("feed.rows"),
        "moves": reg.counter("feed.autotune_moves"),
        "reg": reg,
    }
    self._build()

  # -- graph construction ----------------------------------------------------

  def _build(self) -> None:
    ops = self._plan._ops
    depth_after: Dict[int, int] = self._plan._depths
    default_depth = _DEFAULT_DEPTH
    # source -> buffer -> [stage -> buffer]... -> consumer buffer
    self._buffers.append(_Buffer(depth_after.get(-1, default_depth)))
    idx = 0
    for op in ops:
      kind = op[0]
      if kind == "map":
        body, par = _make_map(op[1], op[2]), True
        name = "map%d" % idx
      elif kind == "filter":
        body, par = _make_filter(op[1], op[2]), True
        name = "filter%d" % idx
      elif kind == "shuffle":
        state = _ShuffleState(op[1], op[2])
        body, par = state.feed, False
        name = "shuffle%d" % idx
      elif kind in ("batch", "slab"):
        state = _AssembleState(
            batch_size=op[1], unroll=op[2], dtype=op[3],
            columns=self._plan._columns, train_mode=self._plan._train_mode)
        body, par = state.feed, False
        name = "assemble"
      else:
        raise ValueError("unknown op %r" % (kind,))
      out = _Buffer(depth_after.get(idx, default_depth))
      emitter = _OrderedEmitter(out, self._det)
      stage = _StageRuntime(name, body, par, self._buffers[-1], emitter,
                            self._stop_evt)
      self._stages.append(stage)
      self._buffers.append(out)
      self.stats["stages"][name] = stage.stats
      idx += 1
    # the source writes into the head buffer through its own emitter
    self._src_emitter = _OrderedEmitter(self._buffers[0], self._det)
    # marker-barrier wiring: every emitter announces marker seqs to the
    # emitter CONSUMING its output buffer (throughput-mode ordering)
    chain = [self._src_emitter] + [s.emitter for s in self._stages]
    for up, down in zip(chain, chain[1:]):
      up.downstream = down
    self._src_stats = {"fetch_s": 0.0, "decode_s": 0.0, "items": 0,
                       "out_wait_s": 0.0}
    self.stats["stages"]["src"] = self._src_stats

  def start(self) -> "GraphExecutor":
    for stage in self._stages:
      stage.spawn(self)
    self._start_source()
    self._tuner = _Autotuner(self)
    self._tuner.start()
    return self

  # -- source ----------------------------------------------------------------

  def _start_source(self) -> None:
    src = self._plan._source
    if src[0] == "pending":
      raise ValueError("cannot start a pipeline() template: bind() it to "
                       "a DataFeed first")
    if src[0] == "interleave":
      t = threading.Thread(target=self._source_interleave, args=(src[1],
                                                                 src[2]),
                           daemon=True, name="tos-pipe-src")
    else:
      t = threading.Thread(target=self._source_single, args=(src,),
                           daemon=True, name="tos-pipe-src")
    self._source_threads.append(t)
    t.start()

  def _emit_source(self, seq: int, item) -> bool:
    return self._src_emitter.emit(seq, [item], self._stop_evt,
                                  self._src_stats)

  def _source_single(self, src) -> None:
    try:
      seq = 0
      for item in self._iter_source(src):
        if self._stop_evt.is_set():
          return
        if not self._emit_source(seq, item):
          return
        seq += 1
        self._src_stats["items"] += 1
        if item[0] == "marker" and item[1] is None:
          return
      self._emit_source(seq, ("marker", None))
    except BaseException as e:  # noqa: BLE001 - re-raised consumer-side
      self._fail(e)

  def _iter_source(self, src):
    """Generator of wire items for one (non-interleave) source spec.
    Legacy row lists with inline markers are split so every downstream
    stage sees markers as standalone items."""
    if src[0] == "chunks":
      for obj in src[1]:
        for item in _split_inline_markers(_normalize_source_item(obj)):
          yield item
          if item[0] == "marker" and item[1] is None:
            return
      return
    # ("feed", feed): chunk-granular fetch off the feed's input channel,
    # with the feed's own liveness discipline (worker tracebacks, hub
    # state, liveness_timeout) — datafeed._fetch_chunk is the one fetch
    # implementation
    from tensorflowonspark_tpu import datafeed as datafeed_mod
    feed = src[1]
    stalled_since = time.monotonic()
    while not self._stop_evt.is_set():
      got = datafeed_mod._fetch_chunk(
          feed._queue_in, datafeed_mod.DEFAULT_FETCH_ROWS,
          timeout=_POLL, stats=self._src_stats)
      if got is None:
        feed._check_liveness(stalled_since)
        if feed.done_feeding:       # hub moved to terminating/stopped
          yield ("marker", None)
          return
        continue
      stalled_since = time.monotonic()
      if got[0] == "marker" and got[1] is None:
        feed.done_feeding = True
        yield ("marker", None)
        return
      for item in _split_inline_markers(got):
        if item[0] == "marker" and item[1] is None:
          feed.done_feeding = True
          yield item
          return
        yield item

  def _source_interleave(self, sources: List["Dataset"], cycle: int) -> None:
    """Parallel interleave across sub-sources: up to ``cycle`` reader
    threads fill per-source buffers; this merger thread emits
    round-robin over the ACTIVATION-ordered rotation (deterministic
    mode blocks on the rotation head, so the merged order is a pure
    function of the source contents) or ready-first in throughput
    mode. A sub-source leaves the rotation only once its reader
    finished AND its buffer drained (no timing race can skip it); a
    freed rotation slot activates the next pending source; ONE
    end-of-feed marker is emitted after all sources end."""
    try:
      pending = list(sources)
      rotation: List[Dict] = []

      def _activate():
        while len(rotation) < cycle and pending:
          ds = pending.pop(0)
          slot = {"buf": _Buffer(max(1, _DEFAULT_DEPTH)), "done": False}

          def _reader(ds=ds, slot=slot):
            try:
              for item in self._iter_source(ds._source):
                if self._stop_evt.is_set():
                  return
                if item[0] == "marker" and item[1] is None:
                  break
                while not self._stop_evt.is_set():
                  if slot["buf"].pipe_put(item, timeout=_POLL):
                    break
            except BaseException as e:  # noqa: BLE001 - consumer-side
              self._fail(e)
            finally:
              # set AFTER the last buffered item: done+empty => truly
              # exhausted, so retiring a slot on that pair is race-free
              slot["done"] = True

          t = threading.Thread(target=_reader, daemon=True,
                               name="tos-pipe-interleave")
          slot["thread"] = t
          rotation.append(slot)
          t.start()

      _activate()
      seq = 0
      p = 0
      while not self._stop_evt.is_set():
        if not rotation:
          if pending:
            _activate()
            continue
          self._emit_source(seq, ("marker", None))
          return
        p %= len(rotation)
        scan = (range(p, p + 1) if self._det
                else range(p, p + len(rotation)))
        advanced = False
        for k in scan:
          slot = rotation[k % len(rotation)]
          got = slot["buf"].pipe_get(
              timeout=_POLL if k == p else 0.001)
          if got is _EMPTY:
            if slot["done"] and not len(slot["buf"]):
              rotation.remove(slot)     # exhausted: leave the rotation
              _activate()
              advanced = True
              break
            continue
          if not self._emit_source(seq, got):
            return
          seq += 1
          self._src_stats["items"] += 1
          p = (rotation.index(slot) + 1) % len(rotation)
          advanced = True
          break
        if not advanced:
          continue
    except BaseException as e:  # noqa: BLE001 - re-raised consumer-side
      self._fail(e)

  # -- workers ---------------------------------------------------------------

  def _stage_worker(self, stage: _StageRuntime, idx: int) -> None:
    del idx   # thread-name cosmetics only; retirement is by live count
    stats = stage.stats
    try:
      while not self._stop_evt.is_set():
        if stage.should_retire():
          return    # the autotuner shrank this pool; retire quietly
        t0 = time.perf_counter()
        got = stage.inbuf.pipe_get(timeout=_POLL)
        stats["in_wait_s"] += time.perf_counter() - t0
        if got is _EMPTY:
          continue
        seq, item = got
        t1 = time.perf_counter()
        outputs = stage.body(item)
        stats["busy_s"] += time.perf_counter() - t1
        stats["items"] += 1
        if not stage.emitter.emit(seq, outputs, self._stop_evt, stats):
          return
    except BaseException as e:  # noqa: BLE001 - re-raised consumer-side
      self._fail(e)

  def _fail(self, error: BaseException) -> None:
    if self._error is None:
      self._error = error
    self._stop_evt.set()

  # -- consumer plane --------------------------------------------------------

  def get(self, timeout: float):
    """Next output item (``("batch", payload)`` / ``("end", None)`` /
    raw wire items for transform-only graphs), or ``None`` on timeout.
    Re-raises a worker error."""
    if self._error is not None:
      raise self._error
    got = self._buffers[-1].pipe_get(timeout=timeout)
    if self._error is not None:
      raise self._error
    if got is _EMPTY:
      return None
    _, item = got
    return item

  def batches(self):
    """Generator over assembled batch payloads until end-of-feed. Stops
    the executor when the stream ends (or the consumer closes it)."""
    try:
      while True:
        item = self.get(timeout=1.0)
        if item is None:
          continue
        kind, payload = item
        if kind == "end" or (kind == "marker" and payload is None):
          return
        if kind in ("batch", "data"):
          self._note_delivery(payload)
          yield payload
    finally:
      self.stop()

  def _note_delivery(self, payload) -> None:
    self.stats["batches"] += 1
    n = _payload_rows(payload)
    self.stats["rows"] += n
    if self._obs_m is not None:
      self._obs_m["batches"].inc()
      if n:
        self._obs_m["rows"].inc(n)

  def stats_snapshot(self) -> obs_metrics.StatsSnapshot:
    """Subtraction baseline over the LIVE ``stats`` dict (per-stage
    dicts included) — the one safe way to read steady-state deltas
    while worker threads keep mutating them."""
    return obs_metrics.snapshot_stats(self.stats)

  def stage_summary(self) -> Dict[str, dict]:
    """Per-stage worker/depth/counter view (autotuner decisions land
    here; the obs mirror and tests/test_datapipe.py read it)."""
    out = {"src": dict(self._src_stats, workers=len(self._source_threads),
                       depth=self._buffers[0].capacity)}
    for stage in self._stages:
      out[stage.name] = dict(stage.stats, workers=stage.target,
                             depth=stage.inbuf.capacity)
    return out

  def stop(self) -> None:
    """Stop every worker and the tuner; buffered items discard."""
    self._stop_evt.set()
    if self._tuner is not None:
      self._tuner.stop()
      # final gauge mirror: a run shorter than one autotune interval
      # must still leave its per-stage totals on the obs wire
      self._tuner._mirror_gauges()
      self._tuner = None
    for t in self._source_threads:
      t.join(timeout=5.0)
    for stage in self._stages:
      for t in stage.threads:
        t.join(timeout=5.0)


def _payload_rows(payload) -> int:
  """Row count of one delivered batch payload (Slab/dict/array/rows)."""
  from tensorflowonspark_tpu.data.readers import Slab
  if isinstance(payload, Slab):
    data = payload.data
    leaf = (next(iter(data.values())) if isinstance(data, dict)
            else data[0] if isinstance(data, tuple) else data)
    return int(leaf.shape[0] * leaf.shape[1]) if hasattr(leaf, "shape") \
        else 0
  if isinstance(payload, dict):
    return len(next(iter(payload.values()))) if payload else 0
  if isinstance(payload, tuple):
    return len(payload[0]) if payload else 0
  if isinstance(payload, chunkcodec.ColumnChunk):
    return payload.n
  try:
    return len(payload)
  except TypeError:
    return 0


# -- the autotuner ------------------------------------------------------------


class _Autotuner(object):
  """Online per-stage parallelism/buffer reallocation (tf.data's
  headline idea, arXiv 2101.12127 §autotuning).

  Every ``TOS_DATA_AUTOTUNE_INTERVAL`` seconds: snapshot-subtract the
  per-stage counters, normalize busy seconds per worker-second
  (utilization), and attribute the bottleneck to the DOMINANT stage —
  the same attribution the obs plane's ``feed_stall`` detector reports,
  used here as the control loop's error signal. One move per pass:

  - a hot (util ≥ 0.5/worker) parallelizable stage gains a worker (up
    to ``TOS_DATA_MAX_WORKERS``), donated by the coldest shrinkable
    pool when one exists;
  - a hot stateful/source stage (map fns can parallelize; shuffle,
    assemble and the source cannot) gets a DEEPER hand-off buffer
    instead (up to ``TOS_DATA_BUFFER_CAP``) so burst skew smooths out;
  - a cold (util < 0.05/worker) multi-worker pool shrinks by one.

  Each move is a structured event: counted (``feed.autotune_moves``),
  ring-buffered on the executor (``autotune_events``), and emitted into
  the obs JSONL via the active recorder (``feed.autotune`` events). The
  pass also mirrors the per-stage gauges (``feed.stage.<name>.*``) the
  detector and ``obs_top`` read — the mirror runs even with autotune
  OFF, so a fixed plan is still observable. Disabled entirely when the
  executor never starts it.
  """

  def __init__(self, executor: GraphExecutor):
    self._ex = executor
    self.interval = max(0.05, _env_float(ENV_DATA_AUTOTUNE_INTERVAL,
                                         _DEFAULT_INTERVAL))
    self._stop_evt = threading.Event()
    self._thread: Optional[threading.Thread] = None
    self._snap = executor.stats_snapshot()
    self._last_t = time.monotonic()
    #: broken passes counted, never raised (the detector-loop invariant)
    self.failures = 0

  def start(self) -> None:
    self._thread = threading.Thread(target=self._run, daemon=True,
                                    name="tos-pipe-tune")
    self._thread.start()

  def stop(self) -> None:
    self._stop_evt.set()
    if self._thread is not None:
      self._thread.join(timeout=5.0)
      self._thread = None

  def _run(self) -> None:
    while not self._stop_evt.wait(self.interval):
      try:
        self.pulse()
      except Exception:  # noqa: BLE001 - the tuner must outlive any
        # single pass bug; a broken pass skips (counted, never raised —
        # the detector-loop invariant) and the pipeline keeps going
        self.failures += 1
        logger.exception("autotune pass failed")

  # one pass, callable directly from tests with a fabricated delta
  def pulse(self) -> Optional[dict]:
    now = time.monotonic()
    dt = max(1e-6, now - self._last_t)
    delta = self._snap.delta()
    self._snap = self._ex.stats_snapshot()
    self._last_t = now
    stages = delta.get("stages", {})
    self._mirror_gauges()
    if not self._ex._autotune:
      return None
    return self._decide(stages, dt)

  def _busy(self, name: str, d: Dict) -> float:
    if name == "src":
      return d.get("fetch_s", 0.0) + d.get("decode_s", 0.0)
    return d.get("busy_s", 0.0)

  def _decide(self, stages: Dict[str, Dict], dt: float) -> Optional[dict]:
    ex = self._ex
    runtimes = {s.name: s for s in ex._stages}
    util = {}
    for name, d in stages.items():
      workers = runtimes[name].target if name in runtimes else 1
      util[name] = self._busy(name, d) / (workers * dt)
    if not util:
      return None
    dominant = max(util, key=util.get)
    move = None
    if util[dominant] >= _HOT_UTIL:
      stage = runtimes.get(dominant)
      if stage is not None and stage.parallelizable \
          and stage.target < ex._max_workers:
        donor = self._coldest(util, runtimes, exclude=dominant)
        if donor is not None:
          donor.shrink()
        stage.grow(ex)
        move = {"action": "add_worker", "stage": dominant,
                "workers": stage.target,
                "donor": donor.name if donor is not None else None}
      else:
        buf = self._inbuf_of(dominant)
        if buf is not None and buf.capacity < ex._buffer_cap:
          buf.set_capacity(min(ex._buffer_cap, buf.capacity * 2))
          move = {"action": "grow_buffer", "stage": dominant,
                  "depth": buf.capacity}
    if move is None:
      donor = self._coldest(util, runtimes)
      if donor is not None:
        donor.shrink()
        move = {"action": "remove_worker", "stage": donor.name,
                "workers": donor.target}
    if move is not None:
      move["util"] = round(util[dominant], 3)
      move["dominant"] = dominant
      self._record(move)
    return move

  def _coldest(self, util, runtimes, exclude=None):
    best, best_u = None, _COLD_UTIL
    for name, u in util.items():
      stage = runtimes.get(name)
      if stage is None or name == exclude or stage.target <= 1:
        continue
      if u < best_u:
        best, best_u = stage, u
    return best

  def _inbuf_of(self, name: str) -> Optional[_Buffer]:
    ex = self._ex
    if name == "src":
      return ex._buffers[0]   # deepen the source's OUT buffer: prefetch
    for stage in ex._stages:
      if stage.name == name:
        return stage.inbuf
    return None

  def _record(self, move: dict) -> None:
    ex = self._ex
    move = dict(move, t=time.time())
    ex.stats["autotune_moves"] += 1
    ex.autotune_events.append(move)
    if ex._obs_m is not None:
      ex._obs_m["moves"].inc()
    rec = ex._rec
    if rec is not None:
      rec.event("feed.autotune",
                **{k: v for k, v in move.items() if k != "t"})
    logger.info("datapipe autotune: %s", move)

  def _mirror_gauges(self) -> None:
    """Mirror live per-stage totals into registry gauges — the wire the
    ``feed_stall`` detector's per-graph-stage attribution and
    ``obs_top``'s ``pipe[...]`` suffix read. Source busy splits into
    the fetch/decode virtual stages so fetch-dominant windows stay
    attributable."""
    m = self._ex._obs_m
    if m is None:
      return
    reg = m["reg"]
    summary = self._ex.stage_summary()
    for name, d in summary.items():
      if name == "src":
        # workers/depth ride the SAME virtual-stage names as the busy
        # gauges so readers keyed on ``*.busy_s`` (obs_top) can pair
        # them — a grow_buffer move on the source shows as fetch/decode
        # depth, not under an unrenderable ``src``
        for virt, busy in (("fetch", d.get("fetch_s", 0.0)),
                           ("decode", d.get("decode_s", 0.0))):
          reg.gauge("feed.stage.%s.busy_s" % virt).set(busy)
          reg.gauge("feed.stage.%s.workers" % virt).set(d["workers"])
          reg.gauge("feed.stage.%s.depth" % virt).set(d["depth"])
      else:
        reg.gauge("feed.stage.%s.busy_s" % name).set(d.get("busy_s", 0.0))
        reg.gauge("feed.stage.%s.workers" % name).set(d["workers"])
        reg.gauge("feed.stage.%s.depth" % name).set(d["depth"])


# -- the declarative graph ----------------------------------------------------


class Dataset(object):
  """A lazy, declarative transform graph over columnar chunk streams.

  Compose sources with transforms; nothing runs until :meth:`batches`
  / :meth:`chunks` / :meth:`start`. Every composition returns a NEW
  ``Dataset`` (the graph is immutable, tf.data-style)::

      ds = (Dataset.from_feed(feed)
              .map(lambda x, y: (x / 255.0, y), columnar=True)
              .shuffle(4096, seed=run_seed)
              .slab(batch_size, unroll)
              .prefetch(4))
      for slab in device_prefetch(ds.batches(), size=2):
          state, losses = loop(state, slab)

  ``deterministic=True`` (default) pins element order — the graph then
  composes with the fused train loop's bit-identical-trajectory
  contract (``from_feed(feed).slab(B, K)`` ≡
  ``data.readers.slab_batches(feed, B, K)`` batch for batch).
  """

  def __init__(self, source, ops: Optional[List] = None,
               columns: Optional[List[str]] = None,
               train_mode: bool = True,
               depths: Optional[Dict[int, int]] = None):
    self._source = source
    self._ops = list(ops or [])
    self._columns = columns
    self._train_mode = train_mode
    self._depths = dict(depths or {})

  # -- sources ---------------------------------------------------------------

  @classmethod
  def from_feed(cls, feed) -> "Dataset":
    """Source over a :class:`datafeed.DataFeed`'s input channel.

    The graph REPLACES the feed's own fixed-depth ``_FetchPipeline``
    (an already-started one is retired) — do not consume the feed via
    ``next_batch*`` while a graph over it is running. Column names come
    from the feed's ``input_mapping`` and marker semantics from its
    ``train_mode``; end-of-feed sets ``feed.done_feeding`` so
    ``should_stop()`` keeps its meaning.
    """
    feed._stop_pipeline()
    return cls(("feed", feed), columns=feed.input_tensors,
               train_mode=feed.train_mode)

  @classmethod
  def pipeline(cls) -> "Dataset":
    """DRIVER-side graph template with a pending source.

    Compose transforms on it, call :meth:`split_pushdown` to carve off
    the feeder segment for ``cluster.run(feed_segment=...)``, then
    :meth:`bind` the consumer remainder to the executor's
    :class:`datafeed.DataFeed` inside the user main fn. A pending graph
    cannot start — :meth:`bind` it first."""
    return cls(("pending", None))

  def bind(self, feed) -> "Dataset":
    """Bind a pending graph (:meth:`pipeline`) to a live feed: the
    :meth:`from_feed` source plus THIS graph's ops. Column names and
    marker semantics come from the feed, exactly as ``from_feed``."""
    if self._source[0] != "pending":
      raise ValueError("bind() is for pipeline() templates; this graph "
                       "already has a %r source" % (self._source[0],))
    feed._stop_pipeline()
    out = Dataset(("feed", feed), self._ops, feed.input_tensors,
                  feed.train_mode, self._depths)
    return out

  def split_pushdown(self):
    """Split this graph at the first non-pushable stage.

    Returns ``(feeder_segment, consumer_dataset)``. Pushable stages are
    the LEADING stateless ``map``/``filter`` ops — ``shuffle``/``batch``/
    ``slab`` and everything after stay consumer-side, and ``interleave``
    sources never push (the merge point is the consumer). Returns
    ``(None, self)`` when nothing pushes (including when
    ``TOS_FEED_PUSHDOWN=0`` disables the split)."""
    if os.environ.get(ENV_FEED_PUSHDOWN, "1").strip().lower() in (
        "0", "false", "off"):
      return None, self
    if self._source[0] == "interleave":
      return None, self
    k = 0
    for op in self._ops:
      if op[0] in ("map", "filter"):
        k += 1
      else:
        break
    if k == 0:
      return None, self
    segment = FeederSegment([tuple(op) for op in self._ops[:k]])
    depths: Dict[int, int] = {}
    for i, d in self._depths.items():
      if i < 0:
        depths[i] = max(d, depths.get(i, 0))
      elif i < k:
        # a prefetch declared after a pushed stage now pads the buffer
        # after the consumer-side source instead
        depths[-1] = max(d, depths.get(-1, 0))
      else:
        depths[i - k] = d
    rest = Dataset(self._source, self._ops[k:], self._columns,
                   self._train_mode, depths)
    return segment, rest

  @classmethod
  def from_chunks(cls, chunks, columns: Optional[List[str]] = None,
                  train_mode: bool = True) -> "Dataset":
    """Source over an iterable of chunks: ``ColumnChunk``\\ s, row
    lists, ``Marker``\\ s (partition boundaries) and a final ``None``
    (end-of-feed; appended implicitly when the iterable just ends)."""
    return cls(("chunks", chunks), columns=columns, train_mode=train_mode)

  @classmethod
  def interleave(cls, sources: Sequence["Dataset"],
                 cycle: Optional[int] = None) -> "Dataset":
    """Parallel interleave across ``sources`` (each a PURE source —
    ``from_chunks``/``from_feed`` with no transforms; transforms
    compose after the merge): up to ``cycle`` sources are read
    concurrently, chunks merged round-robin in source order under
    ``deterministic=True`` or ready-first in throughput mode. One
    end-of-feed marker is emitted after ALL sources end; per-source
    ``EndPartition`` markers ride the merge in stream position."""
    sources = list(sources)
    if not sources:
      raise ValueError("interleave needs at least one source")
    for ds in sources:
      if not isinstance(ds, Dataset):
        raise TypeError("interleave sources must be Datasets")
      if ds._ops:
        raise ValueError(
            "interleave sources must be pure sources (compose transforms "
            "AFTER the interleave; source %r carries ops)" % (ds,))
    cycle = max(1, int(cycle if cycle is not None else len(sources)))
    first = sources[0]
    return cls(("interleave", sources, cycle), columns=first._columns,
               train_mode=first._train_mode)

  # -- transforms ------------------------------------------------------------

  def _extended(self, op) -> "Dataset":
    if self._terminal() is not None:
      raise ValueError("batch()/slab() is terminal: no transforms may "
                       "follow it (prefetch() excepted)")
    return Dataset(self._source, self._ops + [op], self._columns,
                   self._train_mode, self._depths)

  def _terminal(self):
    for op in self._ops:
      if op[0] in ("batch", "slab"):
        return op
    return None

  def map(self, fn: Callable, columnar: bool = False) -> "Dataset":
    """Apply ``fn`` to every element. ``columnar=True``: ``fn`` is
    VECTORIZED — called once per chunk with the column arrays
    (``fn(*cols) -> col | (cols...)``), no per-row Python loop.
    ``columnar=False``: ``fn(row) -> row`` per row; homogeneous results
    re-columnarize so downstream stages stay on the fast path. Markers
    pass through untouched."""
    return self._extended(("map", fn, bool(columnar)))

  def filter(self, pred: Callable, columnar: bool = False) -> "Dataset":
    """Keep elements where ``pred`` holds. ``columnar=True``:
    ``pred(*cols) -> bool mask`` over the chunk (vectorized row
    selection — one fancy-index per column). ``columnar=False``:
    ``pred(row) -> bool`` per row."""
    return self._extended(("filter", pred, bool(columnar)))

  def shuffle(self, buffer_rows: int, seed: int = 0) -> "Dataset":
    """Streaming row-granular shuffle holding ``buffer_rows`` rows
    (vectorized gather on the columnar path). Deterministic per
    ``seed`` + element arrival order; the buffer flushes at markers so
    rows never cross an ``EndPartition``/end-of-feed boundary."""
    return self._extended(("shuffle", int(buffer_rows), int(seed)))

  def batch(self, batch_size: int, dtype=None) -> "Dataset":
    """Terminal: assemble ``batch_size``-row host batches
    (``feed_batches`` semantics: partial final batch at end-of-feed,
    ``EndPartition`` skip/boundary per train/inference mode, empty
    batches skipped)."""
    return self._extended(("batch", int(batch_size), 1, dtype))

  def slab(self, batch_size: int, unroll: int, dtype=None) -> "Dataset":
    """Terminal: assemble ``[unroll, batch_size, ...]``
    :class:`data.readers.Slab`\\ s for the fused train loop
    (``slab_batches`` semantics: short stretches split into the same
    per-step batches, which keeps the fused trajectory bit-identical
    through the graph)."""
    return self._extended(("slab", int(batch_size), int(unroll), dtype))

  def prefetch(self, depth: int) -> "Dataset":
    """Set the hand-off buffer depth AFTER the last declared stage (the
    autotuner may still deepen it further, up to
    ``TOS_DATA_BUFFER_CAP``)."""
    out = Dataset(self._source, self._ops, self._columns, self._train_mode,
                  self._depths)
    out._depths[len(out._ops) - 1] = max(1, int(depth))
    return out

  # -- execution -------------------------------------------------------------

  def start(self, deterministic: bool = True,
            autotune: Optional[bool] = None) -> GraphExecutor:
    """Materialize and start the executor (callers own ``stop()``)."""
    if self._source[0] == "pending":
      raise ValueError("cannot start a pipeline() template: bind() it to "
                       "a DataFeed first")
    return GraphExecutor(self, deterministic=deterministic,
                         autotune=autotune).start()

  def batches(self, deterministic: bool = True,
              autotune: Optional[bool] = None):
    """Run the graph and yield assembled batch payloads (requires a
    ``batch()``/``slab()`` terminal). The generator stops the executor
    when the stream ends or the caller closes it."""
    if self._terminal() is None:
      raise ValueError("batches() needs a batch()/slab() terminal; use "
                       "chunks() for transform-only graphs")
    ex = self.start(deterministic=deterministic, autotune=autotune)
    return ex.batches()

  def chunks(self, deterministic: bool = True,
             autotune: Optional[bool] = None):
    """Run a transform-only graph and yield normalized wire items
    (``("data", ColumnChunk|rows)`` / ``("marker", m)``) until
    end-of-feed."""
    if self._terminal() is not None:
      raise ValueError("chunks() is for transform-only graphs; this one "
                       "has a batch()/slab() terminal — use batches()")
    ex = self.start(deterministic=deterministic, autotune=autotune)

    def _gen():
      try:
        while True:
          item = ex.get(timeout=1.0)
          if item is None:
            continue
          if item[0] == "marker" and item[1] is None:
            return
          yield item
      finally:
        ex.stop()

    return _gen()
