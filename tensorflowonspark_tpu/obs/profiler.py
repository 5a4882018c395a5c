"""Profiling/tracing helpers: the JAX-native TensorBoard story.

The reference's only tracing facility was launching TensorBoard as a
subprocess on chief/worker:0 (reference TFSparkNode.py:292-329 — that part
lives in node.py here). This module adds what TPU users actually profile
with: the JAX profiler — a programmatic trace context writing XProf/
perfetto data TensorBoard can render, and an on-demand capture server.

Moved from ``utils/profiler.py`` into the observability plane (``obs/``):
:class:`StepTimer` now doubles as the training loop's seam into the
metrics registry — when the obs plane is active (``TOS_OBS=1``) each
timed step also lands a ``train.step_ms`` histogram observation, a
``train.steps``/``train.items`` counter bump and a ``train.step`` span,
so the step loop shows up in the shipped deltas and the merged Chrome
trace without any extra user code. The old import path keeps working via
a deprecation shim.
"""

import contextlib
import logging
import os
import time
from typing import Optional

from tensorflowonspark_tpu.obs import metrics as metrics_mod
from tensorflowonspark_tpu.obs import spans as spans_mod

logger = logging.getLogger(__name__)

_server = None


def start_server(port: int = 9999):
  """Start the JAX profiler capture server (connect with TensorBoard's
  profile tab or `xprof`); idempotent per process."""
  global _server
  if _server is None:
    import jax
    _server = jax.profiler.start_server(port)
    logger.info("JAX profiler server listening on port %d", port)
  return _server


@contextlib.contextmanager
def trace(log_dir: str, host_tracer_level: int = 2):
  """Trace a region into ``log_dir`` (viewable in TensorBoard).

  Usage::

      with profiler.trace("/tmp/tb"):
          state, loss = train_step(state, batch)
          jax.block_until_ready(loss)
  """
  import jax
  os.makedirs(log_dir, exist_ok=True)
  with jax.profiler.trace(log_dir):
    yield
  logger.info("profile trace written to %s", log_dir)


# --- step timing / throughput ------------------------------------------------


class StepTimer(object):
  """Wall-clock step statistics with warmup exclusion.

  Usage::

      timer = StepTimer(warmup=2)
      for batch in data:
          with timer.step(items=batch_size):
              state, loss = train_step(state, batch)
              jax.block_until_ready(loss)
      print(timer.summary())   # {steps, mean_ms, p50_ms, p90_ms, items/s}

  The context manager blocks on nothing itself — callers must
  ``block_until_ready`` inside the region or the async dispatch makes every
  step look instant.

  When a metrics registry is active (``obs.metrics.active()``), every
  post-warmup step additionally feeds the registry (``train.steps``,
  ``train.items``, ``train.step_ms``) and records a ``train.step`` span.
  """

  def __init__(self, warmup: int = 2):
    self.warmup = warmup
    self._durations = []
    self._items = []
    self._seen = 0
    # cached once: the step context is the training hot path, and the
    # disabled case must stay a None check; metric HANDLES are cached
    # too (registry get-or-create takes a lock — setup cost, not step
    # cost)
    self._reg = metrics_mod.active()
    self._rec = spans_mod.active()
    if self._reg is not None:
      self._m_steps = self._reg.counter("train.steps")
      self._m_items = self._reg.counter("train.items")
      self._m_step_ms = self._reg.histogram("train.step_ms")

  @contextlib.contextmanager
  def step(self, items: int = 0):
    t0 = time.perf_counter()
    mono0 = time.monotonic() if self._rec is not None else 0.0
    yield
    dt = time.perf_counter() - t0
    self._seen += 1
    if self._seen > self.warmup:
      self._durations.append(dt)
      self._items.append(items)
      if self._reg is not None:
        self._m_steps.inc()
        if items:
          self._m_items.inc(items)
        self._m_step_ms.observe(dt * 1e3)
      if self._rec is not None:
        self._rec.record_span("train.step", mono0, dt, items=items)

  def summary(self) -> dict:
    d = sorted(self._durations)
    if not d:
      return {"steps": 0}
    total = sum(self._durations)
    out = {
        "steps": len(d),
        "mean_ms": 1e3 * total / len(d),
        "p50_ms": 1e3 * d[len(d) // 2],
        "p90_ms": 1e3 * d[min(len(d) - 1, int(len(d) * 0.9))],
    }
    if any(self._items):
      out["items_per_sec"] = sum(self._items) / total
    return out


# --- MFU accounting ----------------------------------------------------------

# bf16 peak FLOP/s per chip by TPU generation (public spec sheets; v5e:
# Google Cloud documentation "TPU v5e", 197 TFLOP/s). THE one peak table:
# only tests/test_profiler.py still keys into it (ROADMAP D12)
PEAK_BF16_FLOPS = {"v4": 275e12, "v5e": 197e12, "v5p": 459e12, "v6e": 918e12}


def resolve_chip_generation(hint: str = "") -> Optional[str]:
  """Map a generation hint / device_kind string to a PEAK_BF16_FLOPS key."""
  text = (hint or "").lower()
  for alias, g in (("v5 lite", "v5e"), ("v5lite", "v5e"), ("v6 lite", "v6e"),
                   ("v6lite", "v6e")):
    if alias in text:
      return g
  # longest key first so "v5p" isn't shadowed by a hypothetical "v5"
  for g in sorted(PEAK_BF16_FLOPS, key=len, reverse=True):
    if g in text:
      return g
  return None


def chip_peak_bf16_flops(device_kind: str):
  """``(generation, bf16 peak FLOP/s)`` for a device_kind as JAX reports it
  (e.g. ``"TPU v5 lite"`` -> ``("v5e", 197e12)``). A device that is not in
  :data:`PEAK_BF16_FLOPS` is an ERROR, never a default: a utilisation
  against an assumed peak is not a measurement."""
  gen = resolve_chip_generation(device_kind)
  if gen is None:
    raise ValueError(
        "unknown device_kind %r: no entry in obs.profiler.PEAK_BF16_FLOPS "
        "(%s) — add the chip with its published peak, do not assume one"
        % (device_kind, ", ".join(sorted(PEAK_BF16_FLOPS))))
  return gen, PEAK_BF16_FLOPS[gen]


def transformer_flops_per_token(n_params: int, num_layers: int,
                                d_model: int, seq_len: int) -> float:
  """Training FLOPs/token, PaLM-style accounting: ``6N`` for the fwd+bwd
  matmuls plus the attention term ``12·L·d_model·S``."""
  return 6.0 * n_params + 12.0 * num_layers * d_model * seq_len


def mfu(flops_per_item: float, items_per_sec: float,
        peak_flops: float) -> float:
  """Model FLOPs utilization against one chip's peak."""
  return flops_per_item * items_per_sec / peak_flops


def device_memory_stats() -> dict:
  """Per-device memory stats (bytes) where the backend reports them."""
  import jax
  out = {}
  for d in jax.devices():
    stats = getattr(d, "memory_stats", lambda: None)()
    if stats:
      out[str(d.id)] = {k: stats[k] for k in
                        ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                        if k in stats}
  return out
