"""Span/event tracing on the monotonic clock, with driver-anchored offsets.

Every process records spans against its OWN ``time.monotonic()`` — the
only clock that never steps backwards under NTP. To land per-executor
traces on one timeline, each executor estimates its offset to the
DRIVER's monotonic clock with an NTP-style exchange piggybacked on
control-plane round-trips (the rendezvous ``BEAT``/``OBS`` replies carry
the server's monotonic timestamp): for a request sent at local ``t0``
and answered at ``t1`` carrying server time ``ts``, the offset sample is
``ts - (t0 + t1) / 2`` with uncertainty ``(t1 - t0) / 2``. The estimator
keeps the minimum-RTT sample of a sliding window, so chaos-injected (or
load-induced) delays inflate individual samples without poisoning the
estimate — one clean round-trip wins.

The recorder is BOUNDED and never blocks (TOS001 by construction): a
full buffer drops the newest record and counts it. Observability must
never wedge the runtime it observes.
"""

import contextlib
import os
import sys
import threading
import time
import uuid
from collections import deque
from typing import Dict, List, Optional

#: span-buffer capacity per process (records held between shipper drains;
#: env registry: TOS008)
ENV_OBS_SPAN_BUFFER = "TOS_OBS_SPAN_BUFFER"

_DEFAULT_CAPACITY = 4096


def new_trace_id() -> str:
  """A fresh request-scoped trace id (16 hex chars, unique across
  processes). Minted once per logical request at the submit boundary
  (``ServingFleet.submit`` / ``ServingEngine.submit``) and stamped onto
  every span the request touches — including across a cross-replica
  failover hop, which is what keeps one request ONE trace."""
  return uuid.uuid4().hex[:16]


def _coerce(v):
  """msgpack/json-safe attribute values (numpy scalars -> builtins)."""
  if isinstance(v, (str, int, float, bool, type(None))):
    return v
  if hasattr(v, "item"):
    try:
      return v.item()
    except Exception:  # noqa: BLE001 - non-scalar array etc.
      return str(v)
  return str(v)


class ClockOffset(object):
  """Min-RTT estimate of (driver monotonic − local monotonic).

  ``update`` is fed by whichever control-plane client sees server
  timestamps (HeartbeatSender beats, ObsShipper ships). ``offset`` is
  the current best estimate (0.0 until the first sample — a driver-side
  recorder simply never updates); ``rtt`` is the uncertainty of that
  sample (error is bounded by ±rtt/2).

  The last ``window`` samples are kept; once the elected sample ages
  out of the window, the minimum-RTT sample OF THE WINDOW is re-elected
  — so a one-off artificially-good sample from a past epoch cannot pin
  the estimate forever (process migration, clock-affecting events), and
  a re-election can never adopt a lone delayed sample while better
  recent ones exist.
  """

  def __init__(self, window: int = 64):
    self.window = int(window)
    self._lock = threading.Lock()
    self.offset = 0.0
    self.rtt = float("inf")
    self.samples = 0
    self._recent: deque = deque(maxlen=max(1, self.window))
    self._since_best = 0

  def update(self, t0: float, server_time: float, t1: float) -> None:
    rtt = max(0.0, t1 - t0)
    sample = server_time - 0.5 * (t0 + t1)
    with self._lock:
      self.samples += 1
      self._since_best += 1
      self._recent.append((rtt, sample))
      if rtt <= self.rtt:
        self.offset = sample
        self.rtt = rtt
        self._since_best = 0
      elif self._since_best >= self.window:
        # the elected sample aged out: re-elect the best RECENT one
        self.rtt, self.offset = min(self._recent, key=lambda rs: rs[0])
        self._since_best = 0

  def snapshot(self) -> dict:
    with self._lock:
      rtt = self.rtt if self.rtt != float("inf") else None
      return {"offset": self.offset, "rtt": rtt, "samples": self.samples}


class SpanRecorder(object):
  """Bounded per-process buffer of finished spans / instant events.

  Records are plain dicts (msgpack/json-safe)::

      {"name": "feed.batch", "ph": "X", "t0": <monotonic>, "dur": <s>,
       "tid": <thread name>, "attrs": {...}}       # span
      {"name": "cluster.stop", "ph": "i", "t0": <monotonic>, ...}  # event

  Request-scoped records additionally carry a TOP-LEVEL ``"trace"`` key
  (the :func:`new_trace_id` minted at submit): the export plane keys
  flow events and the ``obs_report --request`` waterfall on it, so it is
  a record field, not an attr. ``span``/``record_span``/``event`` take
  it as the ``trace=`` kwarg.

  ``add`` never blocks: past ``capacity`` the record is dropped and
  ``dropped`` incremented (the drop counter ships with every OBS delta,
  so lost spans are visible, not silent).
  """

  def __init__(self, capacity: Optional[int] = None,
               clock: Optional[ClockOffset] = None):
    if capacity is None:
      capacity = int(os.environ.get(ENV_OBS_SPAN_BUFFER,
                                    str(_DEFAULT_CAPACITY)))
    self.capacity = max(1, capacity)
    self.clock = clock if clock is not None else ClockOffset()
    self._buf: deque = deque()
    self.dropped = 0
    self.recorded = 0

  # -- hot path --------------------------------------------------------------

  def add(self, record: dict) -> None:
    # len/append under the GIL: worst case a burst briefly overshoots the
    # cap by a few records — bounded either way, and never a lock wait
    if len(self._buf) >= self.capacity:
      self.dropped += 1
      return
    self.recorded += 1
    self._buf.append(record)

  @contextlib.contextmanager
  def span(self, name: str, trace: Optional[str] = None, **attrs):
    t0 = time.monotonic()
    try:
      yield
    finally:
      dur = time.monotonic() - t0
      rec = {"name": name, "ph": "X", "t0": t0, "dur": dur,
             "tid": threading.current_thread().name}
      if trace is not None:
        rec["trace"] = trace
      if attrs:
        rec["attrs"] = {k: _coerce(v) for k, v in attrs.items()}
      self.add(rec)

  def record_span(self, name: str, t0: float, dur: float,
                  trace: Optional[str] = None, **attrs) -> None:
    """Record a span from caller-measured timestamps (for seams that
    already hold a ``perf_counter``-free monotonic pair)."""
    rec = {"name": name, "ph": "X", "t0": t0, "dur": dur,
           "tid": threading.current_thread().name}
    if trace is not None:
      rec["trace"] = trace
    if attrs:
      rec["attrs"] = {k: _coerce(v) for k, v in attrs.items()}
    self.add(rec)

  def event(self, name: str, trace: Optional[str] = None, **attrs) -> None:
    rec = {"name": name, "ph": "i", "t0": time.monotonic(),
           "tid": threading.current_thread().name}
    if trace is not None:
      rec["trace"] = trace
    if attrs:
      rec["attrs"] = {k: _coerce(v) for k, v in attrs.items()}
    self.add(rec)

  # -- drain plane -----------------------------------------------------------

  def __len__(self) -> int:
    return len(self._buf)

  def drain(self, max_records: Optional[int] = None) -> List[dict]:
    """Pop up to ``max_records`` oldest records (all, when None)."""
    out: List[dict] = []
    n = len(self._buf) if max_records is None else max_records
    for _ in range(n):
      try:
        out.append(self._buf.popleft())
      except IndexError:
        break
    return out

  def drop_counts(self) -> Dict[str, int]:
    return {"spans_dropped": self.dropped, "spans_recorded": self.recorded}


# -- the process-active recorder ----------------------------------------------

_active: Optional[SpanRecorder] = None
_active_lock = threading.Lock()


def active() -> Optional[SpanRecorder]:
  """The process recorder, or None when the obs plane is off (mirrors
  ``metrics.active``)."""
  global _active
  if _active is not None:
    return _active
  from tensorflowonspark_tpu.obs import metrics
  if metrics.enabled():
    with _active_lock:
      if _active is None:
        _active = SpanRecorder()
  return _active


def activate(recorder: Optional[SpanRecorder] = None) -> SpanRecorder:
  global _active
  with _active_lock:
    _active = recorder if recorder is not None else SpanRecorder()
    return _active


def deactivate() -> None:
  global _active
  with _active_lock:
    _active = None


# -- the span seam: one region, three sinks -------------------------------------

_tls = threading.local()
_NO_ANNOTATION = contextlib.nullcontext()


def _annotation(name: str):
  """A ``jax.profiler.TraceAnnotation`` for ``name``; a no-op context while
  this process has not loaded JAX. NEVER imports it: ``control/rendezvous.py``
  and ``cluster.py`` import this module in processes that must stay off
  the chip. With no profiler session live the annotation is an
  inactive-flag check."""
  profiler = getattr(sys.modules.get("jax"), "profiler", None)
  return _NO_ANNOTATION if profiler is None \
      else profiler.TraceAnnotation(name)


class Region(object):
  """What ``with region(...) as r`` binds: ``t0`` and ``dur`` (the region's
  one clock reading, ``dur`` set on exit) and, for what is known only once
  the work is done, the writable ``attrs`` of its recorder span and
  ``recorded``, whether the span reaches the recorder at all."""

  __slots__ = ("t0", "dur", "attrs", "recorded", "_counted", "_empty")

  def __init__(self, attrs: dict, recorded: bool = True):
    self.attrs, self.recorded = attrs, recorded
    self.t0 = self.dur = self._counted = self._empty = 0.0


def empty_key(key: str) -> str:
  """The counter of known-drained seconds beside a phase's ``t_*_s``
  counter: ``t_insert_s`` -> ``empty_insert_s``. The prefix is not ``t_``,
  so a reader that takes every ``t_*`` key for a phase still can."""
  return "empty_" + key[2:]


class DeviceQueue(object):
  """What ONE dispatching thread can vouch for about its device's queue.

  The thread that issues every program of a device, and is the only one to
  wait for them, knows without a profiler each interval in which nothing it
  dispatched can still be running: from the return of a blocking read of
  its NEWEST program's output (one stream, in order: the newest done means
  all done) to the return of its next dispatch call. :meth:`dispatched` and
  :meth:`drained` mark the two edges; :func:`region` charges the seconds
  between them to the innermost open region that has a counter,
  ``acc[empty_key(key)]``.

  The sum bounds the device's idle time from below as far as the host can
  see: launch latency, gaps inside a program and the copy back are idle it
  cannot vouch for, and a read of an OLDER program (a newer one in flight)
  proves nothing and counts nothing. One caveat: the runtime may start a
  program somewhat before its dispatch call returns (on a v5e at about two
  thirds of a ``step_many`` call: PERF.md section 6, PR 36), so a dispatch
  phase's share can read over the idle time inside that phase. Owned by
  one thread; no lock.
  """

  __slots__ = ("seq", "_since", "_total")

  def __init__(self):
    self.seq = 0              # the newest dispatch's number
    self._since = None        # drained since this instant; None: not known
    self._total = 0.0         # closed intervals, seconds

  @property
  def known_drained(self) -> bool:
    return self._since is not None

  def dispatched(self) -> int:
    """A dispatch call of the owning thread RETURNED: closes the open
    interval, marks the device possibly busy and returns the dispatch's
    number, to hand to :meth:`drained` once its output has been read."""
    self.unknown()
    self.seq += 1
    return self.seq

  def drained(self, seq: int) -> None:
    """A blocking read of dispatch ``seq``'s output returned (or its output
    was seen ready). Only the newest dispatch's read empties the queue."""
    if seq == self.seq and self._since is None:
      self._since = time.monotonic()

  def unknown(self) -> None:
    """Back to "not known drained" (a crash, a fresh loop thread): what was
    vouched for up to now stays counted, nothing after it is."""
    if self._since is not None:
      self._total += time.monotonic() - self._since
      self._since = None

  def empty_at(self, t: float) -> float:
    """Known-drained seconds up to ``t``, a ``time.monotonic()`` reading no
    older than the last edge. Never decreases."""
    return self._total if self._since is None \
        else self._total + (t - self._since)


@contextlib.contextmanager
def region(name: str, acc: Optional[dict] = None, key: Optional[str] = None,
           trace: Optional[str] = None, record: bool = True,
           queue: Optional[DeviceQueue] = None, **attrs):
  """One timed region of the calling thread, written to three sinks::

      with region("serve.insert", acc=stats, key="t_insert_s"):
          ...

  * counter, always on: on exit ``acc[key]`` grows by the region's SELF
    seconds — its duration less what regions nested inside it put into
    their own counters — so the keys of one thread partition its wall
    time and no second is counted twice (a nested region without a
    counter stays in its parent's). With ``queue`` (the thread's
    :class:`DeviceQueue`) ``acc[empty_key(key)]`` grows, by the same rule
    and on the same two clock readings, by the seconds of them in which
    the device was known drained: never more than the self seconds;
  * trace clock, on while a ``jax.profiler`` session is live: the region
    is a ``TraceAnnotation`` on the host plane of the same ``.xplane.pb``
    as the device's ``XLA Ops`` line;
  * recorder, on with ``TOS_OBS=1``: the span lands in the active
    :class:`SpanRecorder` with ``trace``/attrs, like
    ``SpanRecorder.span``; ``record=False`` keeps a region out of it.
  """
  r = Region(attrs, record)
  parent = getattr(_tls, "top", None)
  with _annotation(name):
    _tls.top = r
    r.t0 = t0 = time.monotonic()
    e0 = queue.empty_at(t0) if queue is not None else 0.0
    try:
      yield r
    finally:
      t1 = time.monotonic()
      r.dur = dur = t1 - t0
      _tls.top = parent
      if acc is not None:
        own = dur - r._counted
        acc[key] += own
        r._counted = dur
        if queue is not None:
          empty = queue.empty_at(t1) - e0
          acc[empty_key(key)] += min(max(empty - r._empty, 0.0), own)
          r._empty = empty
      if parent is not None:
        parent._counted += r._counted
        parent._empty += r._empty
      if r.recorded:
        rec = active()
        if rec is not None:
          rec.record_span(name, r.t0, dur, trace=trace, **r.attrs)
