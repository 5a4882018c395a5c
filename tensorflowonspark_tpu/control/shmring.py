"""Python wrapper for the native shared-memory ring buffer.

The high-throughput alternative to the manager-proxy feed queues
(control/feedhub.py): serialized batches move through POSIX shared memory
(native/shmring.cpp) with no per-row IPC round-trips — the TPU-first
redesign of the reference's feed-plane bottleneck (SURVEY.md §3.2,
row-at-a-time pickled puts at TFSparkNode.py:500-502).

Topology: single producer (the feeder task) / single consumer (the node's
data loader) per ring, which is exactly what the engine guarantees.
Batches are serialized with the columnar chunk codec
(control/chunkcodec.py): homogeneous row chunks ship as raw column
buffers in a msgpack envelope, everything else falls back to cloudpickle
inside the codec.
"""

import ctypes
import logging
import os
import subprocess
from typing import Optional

from tensorflowonspark_tpu.control.chunkcodec import MAX_PAYLOAD as \
    _CODEC_MAX_PAYLOAD


logger = logging.getLogger(__name__)

_SO_PATH = os.path.join(os.path.dirname(__file__), os.pardir, "data",
                        "_shmring_native.so")
_SRC_PATH = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir,
                         "native", "shmring.cpp")
_lib = None
_lib_tried = False


def _compile(so: str) -> bool:
  # build beside the target and rename into place: several executors of
  # one host reach their first use together, and a half-written .so must
  # never be what another process dlopens
  tmp = "%s.%d.tmp" % (so, os.getpid())
  try:
    # -lrt: shm_open/shm_unlink live in librt on older glibc; linking it
    # explicitly is harmless where they moved into libc
    subprocess.run(["g++", "-O3", "-fPIC", "-shared", "-std=c++17",
                    "-o", tmp, os.path.abspath(_SRC_PATH), "-lrt"],
                   check=True, capture_output=True, timeout=120)
    os.replace(tmp, so)
    return True
  except (OSError, subprocess.SubprocessError) as e:
    logger.warning("shmring native build failed: %s", e)
    if os.path.exists(tmp):
      os.unlink(tmp)
    return False


def rebuild() -> bool:
  """Build the native ring from ``native/shmring.cpp`` NOW, replacing any
  library already on disk — so that what runs is what the source says (the
  chip smoke starts here: the ``.so`` is git-ignored and may be stale).
  Call before this process first uses the ring."""
  global _lib, _lib_tried
  if not _compile(os.path.abspath(_SO_PATH)):
    return False
  _lib, _lib_tried = None, False
  return available()


def _bind(so: str):
  lib = ctypes.CDLL(so)
  lib.tos_ring_create.restype = ctypes.c_void_p
  lib.tos_ring_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
  lib.tos_ring_open.restype = ctypes.c_void_p
  lib.tos_ring_open.argtypes = [ctypes.c_char_p]
  lib.tos_ring_write.restype = ctypes.c_int
  lib.tos_ring_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                 ctypes.c_uint32, ctypes.c_int]
  lib.tos_ring_read.restype = ctypes.c_int64
  lib.tos_ring_read.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_uint32, ctypes.c_int]
  lib.tos_ring_close_write.argtypes = [ctypes.c_void_p]
  lib.tos_ring_pending.restype = ctypes.c_uint64
  lib.tos_ring_pending.argtypes = [ctypes.c_void_p]
  lib.tos_ring_free.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_int]
  return lib


def _load():
  global _lib, _lib_tried
  if _lib_tried:
    return _lib
  _lib_tried = True
  so = os.path.abspath(_SO_PATH)
  built = False
  if not os.path.exists(so) and os.path.exists(_SRC_PATH):
    if not _compile(so):
      return None
    built = True
  if not os.path.exists(so):
    return None
  try:
    _lib = _bind(so)
  except (OSError, AttributeError) as e:
    # a PREBUILT .so from a different image can fail to dlopen or miss
    # symbols here (e.g. undefined shm_open when linked without -lrt).
    # available() must gate cleanly — every node bring-up consults it, and
    # leaking a loader error would abort whole-cluster startup over an
    # optional fast path. Rebuild from source once, else fall back.
    logger.warning("shmring native library failed to load (%s)%s", e,
                   "; rebuilding from source" if os.path.exists(_SRC_PATH)
                   else "; falling back to queue transport")
    _lib = None
    if not built and os.path.exists(_SRC_PATH) and _compile(so):
      try:
        _lib = _bind(so)
      except (OSError, AttributeError) as e2:
        logger.warning("rebuilt shmring library still fails to load (%s); "
                       "falling back to queue transport", e2)
        _lib = None
  return _lib


def available() -> bool:
  return _load() is not None


# rings held alive per process (same lifetime pattern as feedhub.hold);
# freed explicitly at shutdown or by the atexit sweep — POSIX shm persists
# past process death, so leaked segments would eat /dev/shm (RAM) until
# reboot
_held = {}
_atexit_registered = False


def hold(key, ring: "ShmRing") -> None:
  global _atexit_registered
  _held[key] = ring
  if not _atexit_registered:
    import atexit
    atexit.register(release_all)
    _atexit_registered = True


def held(key) -> Optional["ShmRing"]:
  return _held.get(key)


def release(key) -> None:
  """Free (and unlink) a held ring."""
  ring = _held.pop(key, None)
  if ring is not None:
    ring.free()


def release_all() -> None:
  for key in list(_held):
    release(key)


def unlink_stale(name: str) -> None:
  """Best-effort unlink of a ring segment whose owner died without freeing
  it (POSIX shm persists past process death). Used by relaunched nodes to
  reap their dead predecessor's segment before creating a fresh,
  generation-suffixed ring."""
  try:
    os.unlink(os.path.join("/dev/shm", name.lstrip("/")))
  except OSError:
    pass


class RingClosed(Exception):
  pass


class RingTimeout(Exception):
  pass


_open_cache = {}


def open_cached(name: str) -> "ShmRing":
  """Open a ring once per process (mmap reuse across feeder tasks)."""
  if name not in _open_cache:
    _open_cache[name] = ShmRing.open(name)
  return _open_cache[name]


class RingQueueAdapter(object):
  """FeedQueue-compatible facade over a ShmRing.

  Exposes the subset of the feed-queue API the feeder tasks and DataFeed
  use (``put``/``put_many``/``get_many``/``task_done``/``join``), so the
  queue and shared-memory transports share one code path. Items travel as
  chunk batches; ``task_done`` is a no-op (the ring's tail pointer IS the
  consumption acknowledgment) and ``join`` waits for the ring to drain.
  """

  def __init__(self, ring: "ShmRing"):
    self._ring = ring
    self._end_sent = False   # synthesized end-of-feed delivered (either API)
    import collections
    self._buffer = collections.deque()

  # keep any single ring payload comfortably below the ring capacity so a
  # write can always be placed after a drain (a record larger than roughly
  # half the ring can wedge against the wrap-around padding); ONE bound
  # shared with put_rows_chunk so both producer paths split identically
  MAX_PAYLOAD = _CODEC_MAX_PAYLOAD

  # producer side ------------------------------------------------------------

  def put_many(self, items, block: bool = True, timeout=None) -> None:
    items = list(items)
    t = None if (block and timeout is None) else (timeout if block else 0.0)
    from tensorflowonspark_tpu.control import chunkcodec
    payload = chunkcodec.encode(items)
    if len(payload) > self.MAX_PAYLOAD and len(items) > 1:
      # split oversized chunks so large rows stream through (parity with
      # FeedQueue.put_many spilling through bounded queues)
      half = len(items) // 2
      self.put_many(items[:half], block=block, timeout=timeout)
      self.put_many(items[half:], block=block, timeout=timeout)
      return
    self._ring.put_payload(payload, timeout=t)

  def put(self, item, block: bool = True, timeout=None) -> None:
    self.put_many([item], block=block, timeout=timeout)

  def put_chunk(self, n: int, payload: bytes, block: bool = True,
                timeout=None) -> None:
    """Enqueue one ALREADY-ENCODED chunk (``n`` is informational here —
    the ring's byte accounting is its own backpressure). Same signature
    as ``FeedQueue.put_chunk`` so producers treat both transports alike;
    callers split oversized chunks at the row level (``node.put_rows_chunk``)
    before reaching either."""
    t = None if (block and timeout is None) else (timeout if block else 0.0)
    self._ring.put_payload(payload, timeout=t)

  def join(self, timeout=None) -> bool:
    import time as _time
    deadline = None if timeout is None else _time.monotonic() + timeout
    while self._ring.pending_bytes() > 0:
      if deadline is not None and _time.monotonic() > deadline:
        return False
      _time.sleep(0.005)
    return True

  # consumer side ------------------------------------------------------------

  def get_many(self, max_items: int, block: bool = True, timeout=None):
    if not self._buffer:
      if self._end_sent:
        return []
      try:
        got = self._ring.get_batch(
            timeout=(timeout if timeout is not None else
                     (None if block else 0.0)))
        self._buffer.extend(got)
      except RingTimeout:
        return []
      except RingClosed:
        # producer closed the ring without an in-band end-of-feed marker
        # (e.g. it died): synthesize one, exactly once, so
        # DataFeed.next_batch reaches done_feeding instead of polling an
        # empty closed ring forever — and later calls return [] so
        # DataFeed.terminate's consecutive-empty drain loop still ends
        self._end_sent = True
        return [None]
    out = []
    while self._buffer and len(out) < max_items:
      out.append(self._buffer.popleft())
    return out

  def get_chunk(self, max_rows: int = 1024, block: bool = True,
                timeout=None):
    """Dequeue ONE chunk without materializing rows; ``None`` on timeout.

    Returns the consumer-facing union ``("data", ColumnChunk | row_list)``
    or ``("marker", m)``: one ring payload maps to one chunk, decoded via
    ``chunkcodec.decode_columns`` with the scratch buffer passed straight
    into msgpack (no whole-payload copy; the column views are backed by
    msgpack-owned bytes, so producer slot reuse after ``task_done`` cannot
    touch a handed-off chunk). Single-marker chunks (a ``put(None)`` /
    ``put(EndPartition())`` from the producer) surface as chunk-boundary
    ``("marker", m)`` envelopes; a ring closed without an in-band marker
    synthesizes ``("marker", None)`` exactly once.
    """
    from tensorflowonspark_tpu.control import chunkcodec
    if self._buffer:
      # rows left over from interleaved legacy get_many use
      out = []
      while self._buffer and len(out) < max_rows:
        out.append(self._buffer.popleft())
      return ("rows", out)
    if self._end_sent:
      return None
    try:
      payload = self._ring.get_payload(
          timeout=(timeout if timeout is not None else
                   (None if block else 0.0)))
    except RingTimeout:
      return None
    except RingClosed:
      self._end_sent = True
      return ("marker", None)
    return chunkcodec.classify_decoded(chunkcodec.decode_columns(payload))

  def task_done(self, n: int = 1) -> None:
    pass

  def qsize(self) -> int:
    return len(self._buffer) + (1 if self._ring.pending_bytes() else 0)

  def empty(self) -> bool:
    return self.qsize() == 0


class ShmRing(object):
  """One endpoint of a shared-memory batch ring."""

  def __init__(self, name: str, handle, lib, owner: bool):
    self.name = name
    self._h = handle
    self._lib = lib
    self._owner = owner
    self._buf = ctypes.create_string_buffer(1 << 20)

  # -- constructors ----------------------------------------------------------

  @classmethod
  def create(cls, name: str, capacity: int = 64 * 1024 * 1024) -> "ShmRing":
    lib = _load()
    if lib is None:
      raise RuntimeError("native shmring unavailable (no toolchain?)")
    h = lib.tos_ring_create(name.encode(), capacity)
    if not h:
      raise OSError("failed to create shm ring %r" % name)
    return cls(name, h, lib, owner=True)

  @classmethod
  def open(cls, name: str) -> "ShmRing":
    lib = _load()
    if lib is None:
      raise RuntimeError("native shmring unavailable (no toolchain?)")
    h = lib.tos_ring_open(name.encode())
    if not h:
      raise OSError("failed to open shm ring %r" % name)
    return cls(name, h, lib, owner=False)

  # -- batch API -------------------------------------------------------------

  def put_batch(self, batch, timeout: Optional[float] = None) -> None:
    """Serialize and enqueue one batch (a list of rows / arrays pytree).

    Homogeneous row lists go through the columnar chunk codec (raw column
    buffers, no pickle); everything else falls back to cloudpickle inside
    the codec."""
    from tensorflowonspark_tpu.control import chunkcodec
    self.put_payload(chunkcodec.encode(batch), timeout=timeout)

  def put_payload(self, payload: bytes,
                  timeout: Optional[float] = None) -> None:
    """Enqueue an already-serialized batch."""
    rc = self._lib.tos_ring_write(
        self._h, payload, len(payload),
        -1 if timeout is None else int(timeout * 1000))
    if rc == 0:
      return
    if rc == 1:
      raise RingTimeout("ring %r write timed out" % self.name)
    if rc == 2:
      raise RingClosed("ring %r is closed" % self.name)
    raise ValueError("batch of %d bytes exceeds ring capacity"
                     % len(payload))

  def get_batch(self, timeout: Optional[float] = None):
    """Dequeue one batch; raises RingClosed when drained after close."""
    from tensorflowonspark_tpu.control import chunkcodec
    return chunkcodec.decode(self.get_payload(timeout=timeout))

  def get_payload(self, timeout: Optional[float] = None):
    """Dequeue one raw serialized record as a memoryview over the reader
    scratch buffer — ZERO-COPY hand-off to the codec. The view is only
    valid until the next read: decode before reading again (msgpack
    copies bin/str data into owned bytes during the parse, so decoded
    chunks survive scratch reuse)."""
    t = -1 if timeout is None else int(timeout * 1000)
    while True:
      n = self._lib.tos_ring_read(self._h, self._buf, len(self._buf), t)
      if n >= 0:
        return memoryview(self._buf)[:n]
      if n == -1:
        raise RingTimeout("ring %r read timed out" % self.name)
      if n == -2:
        raise RingClosed("ring %r closed and drained" % self.name)
      # -3: record larger than our scratch — grow and retry
      self._buf = ctypes.create_string_buffer(len(self._buf) * 2)

  def close_write(self) -> None:
    """Producer signals end-of-stream (consumer drains then RingClosed)."""
    self._lib.tos_ring_close_write(self._h)

  def pending_bytes(self) -> int:
    return self._lib.tos_ring_pending(self._h)

  def free(self) -> None:
    if self._h:
      self._lib.tos_ring_free(self._h, self.name.encode(),
                              1 if self._owner else 0)
      self._h = None

  def __enter__(self):
    return self

  def __exit__(self, *exc):
    self.free()
