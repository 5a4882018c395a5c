"""Compile/device telemetry tier: recompile sentinel, HLO cost, memory.

"Exploring the limits of Concurrency in ML Training on Google TPUs"
(PAPERS.md) identifies compile-time and step-time variance as the
dominant at-scale failure signals; a recompile storm (a jit seam whose
cache keys on data-dependent shapes) silently multiplies step time by
the compile cost. This module feeds those signals into the SAME process
registry the rest of the obs plane ships driver-ward, so the anomaly
detectors (``obs.anomaly``) and the live monitor (``tools/obs_top.py``)
see them online instead of post-mortem:

- **Recompile sentinel** — :func:`install_compile_listener` hooks
  ``jax.monitoring``'s backend-compile duration events (where this jax
  exposes them) into ``xla.compiles`` / ``xla.compile_ms`` plus one
  retroactive ``compile`` span per compilation. Per-function labels
  come from :func:`note_trace` calls placed INSIDE our own jit seams
  (``models/transformer.py`` decode loops, ``serving/slots.py`` slab
  ops, ``parallel/sharding.py`` train step): jit re-traces the Python
  body exactly once per new cache entry, so a trace count is a compile
  count per seam (``xla.compiles.<label>``; an explicit ``.lower()``
  retraces too — the cost-capture path below is the only caller).
- **HLO cost capture** — :func:`capture_cost` runs
  ``jitted.lower(*args).cost_analysis()`` once per (label, arg-shape
  fingerprint) and records ``xla.cost.<label>.flops`` /
  ``xla.cost.<label>.bytes`` gauges, so the roofline-relevant numbers
  for the train and serving steps ride the OBS wire.
- **Device-memory gauges** — :func:`make_memory_sampler` folds
  ``obs.profiler.device_memory_stats`` (exported API that previously
  nothing sampled) into ``device.bytes_in_use`` / ``device.peak_bytes``
  / ``device.bytes_limit`` gauges; ``node._start_obs_shipper`` runs it
  on the ObsShipper cadence so watermarks ship with every delta.

Everything honors the plane's invariant: zero work when ``TOS_OBS=0``
(callers guard on :func:`metrics.active`), failures counted not raised,
and the listener/sampler hot paths are a few GIL-guarded updates per
COMPILE or per SHIP — never per step. ``TOS_OBS_DEVICE=0`` switches
just this tier off while the rest of the plane keeps running.
"""

import logging
import os
import threading
import time
from typing import Callable, Dict, Optional

from tensorflowonspark_tpu.obs import metrics as metrics_mod
from tensorflowonspark_tpu.obs import spans as spans_mod

logger = logging.getLogger(__name__)

#: device/compile tier gate — default ON whenever ``TOS_OBS=1``; set to
#: ``0`` to keep the base plane without the jax.monitoring hook and
#: memory sampler (env registry: TOS008)
ENV_OBS_DEVICE = "TOS_OBS_DEVICE"

#: compile durations are ms-to-minutes: dedicated wide bucket bounds
COMPILE_MS_BUCKETS = (1.0, 5.0, 10.0, 50.0, 100.0, 500.0, 1000.0,
                      5000.0, 15000.0, 60000.0, 300000.0)

#: the jax.monitoring duration event one backend compilation emits
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

#: the jax.monitoring instant event one PERSISTENT-cache hit emits
#: (utils.compile_cache.setup, called at node bring-up). NOTE: jax's
#: ``_COMPILE_EVENT`` duration event WRAPS compile_or_get_cached, so it
#: fires on hits too — this instant event fires INSIDE that region, and
#: each one arms a ``_pending_hits`` discount that absorbs its paired
#: duration event. Net effect: hits surface as ``xla.cache_hits`` and
#: never count as fresh compiles (the recompile-storm detector must not
#: treat a relaunched executor's warm loads as a storm)
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_install_lock = threading.Lock()
_monitoring_hooked = False
#: persistent-cache hits whose enclosing backend-compile duration event
#: has not arrived yet: jax's duration event WRAPS compile_or_get_cached,
#: so it fires for cache hits too — each hit arms one discount so the
#: paired duration event is counted as a load, not a fresh compile
_pending_hits = {"n": 0}
_pending_lock = threading.Lock()
_cost_seen: set = set()
_cost_lock = threading.Lock()
#: sentinel-internal failures (counted, never raised — the tier must not
#: poison the compile/trace it observes); mutable dict, not a bare int,
#: so the hot-path handlers can count without `global`
SENTINEL_ERRORS = {"count": 0}


def device_tier_enabled() -> bool:
  """True when the obs plane is on AND the device tier isn't opted out."""
  return metrics_mod.enabled() and \
      os.environ.get(ENV_OBS_DEVICE, "1") not in ("0",)


# -- recompile sentinel -------------------------------------------------------


def _on_compile_duration(event: str, duration: float, **kwargs) -> None:
  """jax.monitoring listener: one backend compile happened somewhere in
  this process. Looks the registry up at EVENT time (listeners are
  process-global and outlive any one registry), so with the plane off
  this is one None check per compile — and compiles are rare."""
  if event != _COMPILE_EVENT:
    return
  with _pending_lock:
    if _pending_hits["n"] > 0:
      # this "compile" was a persistent-cache load (the hit event fired
      # inside the wrapped lookup): already counted as xla.cache_hits,
      # must not count as a fresh compile or relaunched executors with a
      # warm compile cache read as a recompile storm
      _pending_hits["n"] -= 1
      return
  reg = metrics_mod.active()
  if reg is None:
    return
  try:
    reg.counter("xla.compiles").inc()
    reg.histogram("xla.compile_ms", COMPILE_MS_BUCKETS).observe(
        duration * 1e3)
    rec = spans_mod.active()
    if rec is not None:
      # retroactive span: the event fires when the compile ENDS
      rec.record_span("compile", time.monotonic() - duration, duration)
  except Exception:  # noqa: BLE001 - telemetry must never break a compile
    SENTINEL_ERRORS["count"] += 1


def _on_event(event: str, **kwargs) -> None:
  """jax.monitoring instant-event listener: persistent-cache hits.

  Each hit also arms one compile-duration discount (``_pending_hits``)
  — the hit fires INSIDE the duration-event region, so the discount is
  armed before the duration event it must absorb."""
  if event != _CACHE_HIT_EVENT:
    return
  with _pending_lock:
    _pending_hits["n"] += 1
  reg = metrics_mod.active()
  if reg is None:
    return
  try:
    reg.counter("xla.cache_hits").inc()
  except Exception:  # noqa: BLE001 - telemetry must never break a load
    SENTINEL_ERRORS["count"] += 1


def install_compile_listener() -> bool:
  """Hook jax.monitoring's compile events into the registry (idempotent).

  Two listeners: backend-compile durations → ``xla.compiles`` (fresh
  compiles only — the duration event wraps jax's cache lookup and fires
  on persistent-cache hits too, so each hit's instant event arms a
  discount that absorbs its paired duration event) and cache-hit
  instants → ``xla.cache_hits``.
  Returns True when the hooks are (already) installed; False when this
  jax has no usable ``jax.monitoring`` — :func:`note_trace` then counts
  the global ``xla.compiles`` from our own seams as the fallback.
  """
  global _monitoring_hooked
  with _install_lock:
    if _monitoring_hooked:
      return True
    try:
      from jax import monitoring
      monitoring.register_event_duration_secs_listener(_on_compile_duration)
      monitoring.register_event_listener(_on_event)
    except Exception as e:  # noqa: BLE001 - older jax / stub backends:
      # the tracing-counter fallback still covers our own seams
      logger.info("jax.monitoring unavailable (%s); recompile sentinel "
                  "falls back to per-seam trace counters", e)
      return False
    _monitoring_hooked = True
    return True


def monitoring_hooked() -> bool:
  return _monitoring_hooked


def note_trace(label: str) -> None:
  """Call at the TOP of a jit-compiled function body: fires once per
  (re)trace — i.e. once per new jit-cache entry — giving the recompile
  sentinel its per-function labels (``xla.compiles.<label>``).

  Host-side effect at trace time by design (the traced computation never
  contains it). When ``jax.monitoring`` is absent the seam also counts
  the global ``xla.compiles`` so the storm detector stays armed.
  """
  reg = metrics_mod.active()
  if reg is None:
    return
  try:
    reg.counter("xla.compiles." + label).inc()
    if not _monitoring_hooked:
      reg.counter("xla.compiles").inc()
    rec = spans_mod.active()
    if rec is not None:
      rec.event("compile.trace", label=label)
  except Exception:  # noqa: BLE001 - a telemetry bug must not poison a trace
    SENTINEL_ERRORS["count"] += 1


# -- HLO cost capture ---------------------------------------------------------


def _shape_fingerprint(args, kwargs) -> str:
  """Stable (shape, dtype) fingerprint of a jitted call's arguments."""
  import jax
  parts = []
  for leaf in jax.tree.leaves((args, kwargs)):
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None:
      parts.append(type(leaf).__name__)
    else:
      parts.append("%s%s" % (dtype, list(shape)))
  return ";".join(parts)


def capture_cost(label: str, jitted_fn, *args, **kwargs) -> Optional[dict]:
  """Record ``lowered.cost_analysis()`` flops/bytes for one jitted seam,
  once per distinct argument-shape fingerprint.

  Gauges: ``xla.cost.<label>.flops`` and ``xla.cost.<label>.bytes``
  (bytes accessed), plus an ``xla.cost.captures`` counter. The lowering
  retraces the function (bumping its :func:`note_trace` counter once —
  the only non-compile caller); failures are counted into
  ``xla.cost.failures`` and never raised. Returns the captured dict, or
  None (disabled / already seen / analysis unavailable).
  """
  reg = metrics_mod.active()
  # gate on the live registry (explicit activation counts — tests,
  # embedders) plus the tier opt-out, not on the TOS_OBS env alone
  if reg is None or os.environ.get(ENV_OBS_DEVICE, "1") in ("0",):
    return None
  key = (label, _shape_fingerprint(args, kwargs))
  with _cost_lock:
    if key in _cost_seen:
      return None
    _cost_seen.add(key)
  try:
    cost = jitted_fn.lower(*args, **kwargs).cost_analysis()
    # jax has returned both a dict and a per-device list of dicts
    if isinstance(cost, (list, tuple)):
      cost = cost[0] if cost else {}
    flops = float(cost.get("flops", 0.0) or 0.0)
    nbytes = float(cost.get("bytes accessed", 0.0) or 0.0)
    reg.gauge("xla.cost.%s.flops" % label).set(flops)
    reg.gauge("xla.cost.%s.bytes" % label).set(nbytes)
    reg.counter("xla.cost.captures").inc()
    rec = spans_mod.active()
    if rec is not None:
      rec.event("compile.cost", label=label, flops=flops, bytes=nbytes)
    return {"flops": flops, "bytes": nbytes}
  except Exception as e:  # noqa: BLE001 - cost analysis is best-effort
    # telemetry (backends without HLO properties, AOT-only paths)
    reg.counter("xla.cost.failures").inc()
    logger.debug("cost capture for %r failed: %s", label, e)
    return None


def reset_cost_cache() -> None:
  """Forget per-process cost fingerprints (test isolation helper)."""
  with _cost_lock:
    _cost_seen.clear()


# -- device-memory gauges -----------------------------------------------------


def make_memory_sampler(registry: metrics_mod.MetricsRegistry,
                        stats_fn: Optional[Callable[[], Dict]] = None
                        ) -> Callable[[], None]:
  """A sampler closure for :meth:`ObsShipper.add_sampler`: reads
  ``device_memory_stats`` and sets process-wide watermark gauges.

  ``device.bytes_in_use`` / ``device.bytes_limit`` sum across this
  process's local devices (the footprint that OOMs together);
  ``device.peak_bytes`` is the max single-device peak (the first chip to
  hit its limit is the one that kills the step). Backends that report no
  memory stats (typical CPU) leave the gauges untouched — the sampler
  stays a cheap no-op.
  """
  if stats_fn is None:
    from tensorflowonspark_tpu.obs import profiler
    stats_fn = profiler.device_memory_stats
  g_use = registry.gauge("device.bytes_in_use")
  g_peak = registry.gauge("device.peak_bytes")
  g_limit = registry.gauge("device.bytes_limit")
  c_samples = registry.counter("device.mem_samples")
  last = {}

  def sample() -> None:
    stats = stats_fn()
    if not stats:
      return
    in_use = sum(d.get("bytes_in_use", 0) for d in stats.values())
    peak = max((d.get("peak_bytes_in_use", 0) for d in stats.values()),
               default=0)
    limit = sum(d.get("bytes_limit", 0) for d in stats.values())
    cur = (in_use, peak, limit)
    if last.get("v") == cur:
      # static memory on an idle executor: touch NOTHING, or the
      # per-round counter bump alone would wake the shipper's wire
      # every interval forever (the idle short-circuit's whole point)
      return
    last["v"] = cur
    g_use.set(in_use)
    if peak:
      g_peak.set(peak)
    if limit:
      g_limit.set(limit)
    c_samples.inc()

  return sample


def install(shipper=None) -> bool:
  """Bring the whole device tier up for this process (idempotent).

  Installs the compile listener; when a ``shipper`` is given, registers
  the memory sampler on its cadence so the gauges ride every delta.
  No-op (False) when the tier is disabled.
  """
  if not device_tier_enabled():
    return False
  install_compile_listener()
  if shipper is not None and shipper.registry is not None:
    shipper.add_sampler(make_memory_sampler(shipper.registry))
  return True
