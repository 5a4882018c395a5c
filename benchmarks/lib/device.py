"""Helpers for the ONE process that holds the chip (they import JAX; the
benchmark's parent never calls them)."""

import os
import time


class NoAccelerator(RuntimeError):
  pass


def device_record(chips: int, rehearse: bool) -> dict:
  """The device as JAX reports it; anything but enough TPU chips raises
  outside ``--rehearse``."""
  import jax
  devs = jax.devices()
  rec = dict(platform=devs[0].platform, kind=devs[0].device_kind,
             count=len(devs))
  if not rehearse:
    if rec["platform"] != "tpu":
      raise NoAccelerator("the benchmark needs a TPU, JAX found %r (%s)"
                          % (rec["platform"], rec["kind"]))
    if rec["count"] < chips:
      raise NoAccelerator("the cell asks for %d chip(s), JAX found %d"
                          % (chips, rec["count"]))
    from tensorflowonspark_tpu import ops
    if ops.pallas_interpret() or not ops.pallas_kernels_enabled():
      raise NoAccelerator("Pallas kernels are off or in interpret mode on "
                          "the chip path")
  return rec


def memory_peak_bytes() -> int:
  """Peak device memory on the fullest local chip (0 where the backend does
  not report it, as on the CPU).

  The TPU runtime keeps two counters: ``bytes_in_use`` (live arrays) and
  ``bytes_reserved`` (what loaded programs hold for their scratch: the train
  program's 10.9 GB of temporaries show up only there).  A chip's memory is
  taken by both, so the peak is the live bytes now plus the most ever
  reserved, and never less than the most ever live.  Read it when the window
  closes, before the reference runs."""
  import jax
  peak = 0
  for d in jax.local_devices():
    stats = d.memory_stats() or {}
    live_peak = int(stats.get("peak_bytes_in_use", 0))
    now = int(stats.get("bytes_in_use", 0)) + int(
        stats.get("peak_bytes_reserved", 0))
    peak = max(peak, live_peak, now)
  return peak


def memory_stats() -> dict:
  """The first local device's memory counters, for an earlier line."""
  import jax
  return {k: int(v) for k, v in
          (jax.local_devices()[0].memory_stats() or {}).items()
          if isinstance(v, (int, float))}


class CompileCounter(object):
  """Counts backend compilations (cache loads included) and persistent-cache
  hits/misses through ``jax.monitoring``; ``mark()`` then ``since_mark()``
  gives the compilations inside the window (expected 0)."""

  _COMPILE = "/jax/core/compile/backend_compile_duration"
  _HIT = "/jax/compilation_cache/cache_hits"
  _MISS = "/jax/compilation_cache/cache_misses"

  def __init__(self):
    from jax import monitoring
    self.compiles = 0
    self.compile_s = 0.0
    self.hits = 0
    self.misses = 0
    self._mark = 0
    monitoring.register_event_duration_secs_listener(self._on_duration)
    monitoring.register_event_listener(self._on_event)

  def _on_duration(self, event: str, secs: float, **kw) -> None:
    if event == self._COMPILE:
      self.compiles += 1
      self.compile_s += secs

  def _on_event(self, event: str, **kw) -> None:
    if event == self._HIT:
      self.hits += 1
    elif event == self._MISS:
      self.misses += 1

  def mark(self) -> None:
    self._mark = self.compiles

  def since_mark(self) -> int:
    return self.compiles - self._mark

  def record(self) -> dict:
    return dict(compiles=self.compiles, compile_s=self.compile_s,
                cache_hits=self.hits, cache_misses=self.misses,
                compiles_in_window=self.since_mark())


def cache_every_program() -> None:
  """Let the persistent cache keep the small programs too (JAX's floor is a
  second of compile time): a run's few hundred small compilations are then
  loads as well, and set-up is shorter and steadier.  Set-up only: nothing
  about a compiled program changes."""
  import jax
  jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
  jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class Tracer(object):
  """``--trace 1``: profile ``duration`` seconds of the SAME steady load,
  right after the measured window has closed (starting and stopping the
  profiler stalls the host for seconds, which would otherwise sit inside the
  window), then reduce the ``.xplane.pb`` with :mod:`benchmarks.lib.trace`.

  ``with tracer.running(): <keep the load going>``; ``expired()`` says when
  the stretch is over."""

  def __init__(self, enabled: bool, directory: str, duration: float):
    self.enabled = enabled
    self.dir = directory
    self.duration = duration
    self.state = "idle" if enabled else "off"
    self.t_started = None

  def start(self) -> None:
    import jax
    if self.state == "idle":
      os.makedirs(self.dir, exist_ok=True)
      jax.profiler.start_trace(self.dir)
      self.state, self.t_started = "on", time.monotonic()

  def expired(self) -> bool:
    return self.state != "on" or \
        time.monotonic() - self.t_started >= self.duration

  def stop(self) -> None:
    import jax
    if self.state == "on":
      jax.profiler.stop_trace()
      self.state = "done"

  def reduce(self):
    if self.state != "done":
      return None
    from benchmarks.lib import trace
    return trace.reduce_directory(self.dir)
