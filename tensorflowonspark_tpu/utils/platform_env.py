"""Process-level JAX platform pinning for CPU-only multi-device work.

The program runs two ways (README, Testing): on the CPU for tests — a
virtual multi-device platform, which is what this module sets up — and on
the chip (``chip_smoke.py``, ``benchmarks/run.py``), where nothing here is
called and JAX takes the TPU it finds.

One shared implementation for tests (``tests/conftest.py``), the driver
entry (``__graft_entry__.py``) and the ``Makefile`` dryrun — keeps them from
drifting. Must be called before jax's backend initializes; raises if that
already happened with the wrong platform, because silently proceeding would
run a "CPU" check on whatever device the backend took.
"""

import os
import re


def backend_initialized() -> bool:
  """True when jax's backend is already up (best effort — private API)."""
  try:
    from jax._src import xla_bridge
    return bool(xla_bridge.backends_are_initialized())
  except Exception:  # noqa: BLE001 - private API; degrade to "unknown"
    return False


def force_cpu_platform(n_devices: int = 8) -> None:
  """Force this process onto a virtual CPU platform of >= ``n_devices``.

  A caller-supplied ``--xla_force_host_platform_device_count`` larger than
  ``n_devices`` is preserved (so e.g. ``XLA_FLAGS=...=16 pytest`` still sees
  16 devices); a smaller one is grown to ``n_devices``. Safe to call multiple
  times. Child processes inherit the environment, so calling this before
  spawning executors keeps the whole tree CPU-only.

  Raises:
    RuntimeError: if jax's backend was already initialized on a non-CPU
      platform or with too few devices (too late to redirect — the caller
      must sanitize earlier).
  """
  os.environ["JAX_PLATFORMS"] = "cpu"
  flags = os.environ.get("XLA_FLAGS", "")
  existing = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
  count = max(n_devices, int(existing.group(1)) if existing else 0)
  opt = "--xla_force_host_platform_device_count=%d" % count
  if existing:
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", opt, flags)
  else:
    flags = (flags + " " + opt).strip()
  os.environ["XLA_FLAGS"] = flags

  try:
    import jax
  except ImportError:
    return  # nothing imported yet; the env vars above are sufficient
  # jax reads JAX_PLATFORMS once, at import: where it was imported before
  # this call the env var alone no longer wins
  jax.config.update("jax_platforms", "cpu")
  if backend_initialized():
    if jax.default_backend() != "cpu":
      raise RuntimeError(
          "force_cpu_platform called after jax initialized backend %r — "
          "sanitize before any jax computation" % jax.default_backend())
    if jax.device_count() < n_devices:
      raise RuntimeError(
          "force_cpu_platform: jax already initialized with %d CPU devices, "
          "cannot grow to %d — sanitize before any jax computation"
          % (jax.device_count(), n_devices))
