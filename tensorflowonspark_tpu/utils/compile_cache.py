"""One placement rule for JAX's persistent compilation cache.

Every process of this repo that is about to jit calls :func:`setup` first:
node bring-up (both the foreground and the spawned background runner),
the serving host, ``chip_smoke.py`` and the runners under
``benchmarks/runners/``. The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads the variable itself, and
  this code sets NO directory of its own (whoever runs the program decides
  where the cache lives, e.g. a chip machine that keeps it between calls);
* unset — the cache goes to ONE fixed path inside the checkout,
  ``<repo>/.jax_cache`` (git-ignored), resolved from this file's own
  location. Never from the cwd (LocalEngine executors ``chdir`` into a
  ``mkdtemp`` directory), never from a temp name, pid or time: the path
  is part of the cache key, so a directory that moves never hits.

JAX's own floors stay as they are (only compiles that took a second or
more are written), so the small CPU-harness programs cost no disk.

One more thing has to hold for a cache to hit: the key must not depend on
WHO called. By default JAX writes the Python traceback of each traced
equation into MLIR locations; the key strips those from the program, but a
Pallas kernel travels as a serialized Mosaic module INSIDE its
``tpu_custom_call``, debug info and all — so the same train program traced
from ``TrainLoop.lower`` and from ``TrainLoop.__call__``, or from two
different launchers, got two keys (first seen on the v5e: a second process
building the identical program recompiled it for 26 s). :func:`setup`
therefore turns the caller frames off
(``jax_include_full_tracebacks_in_locations``); Mosaic diagnostics then
name the failing line without the call chain above it.
"""

import os

#: JAX's own variable (read by jax.config at import); listed here only so
#: the rule above has one name to point at
ENV_JAX_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the fixed in-checkout default (git-ignored)
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def cache_dir() -> str:
  """Where this process's persistent compile cache lives."""
  return os.environ.get(ENV_JAX_CACHE_DIR) or DEFAULT_DIR


def setup() -> str:
  """Place the persistent compilation cache; call before the first jit.

  Returns the directory in use. With ``JAX_COMPILATION_CACHE_DIR`` set this
  sets no directory of its own.
  """
  import jax
  # the key must not depend on the caller's stack (module docstring)
  jax.config.update("jax_include_full_tracebacks_in_locations", False)
  if os.environ.get(ENV_JAX_CACHE_DIR):
    return os.environ[ENV_JAX_CACHE_DIR]
  os.makedirs(DEFAULT_DIR, exist_ok=True)
  jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
  return DEFAULT_DIR


class HitCounter(object):
  """Counts this process's persistent-cache hits and misses through
  ``jax.monitoring`` (how the chip smoke reports "did the second process
  load instead of compile")."""

  _HIT = "/jax/compilation_cache/cache_hits"
  _MISS = "/jax/compilation_cache/cache_misses"

  def __init__(self):
    self.hits = 0
    self.misses = 0
    from jax import monitoring
    monitoring.register_event_listener(self._on_event)

  def _on_event(self, event: str, **kwargs) -> None:
    if event == self._HIT:
      self.hits += 1
    elif event == self._MISS:
      self.misses += 1
