"""Test configuration: force a virtual 8-device CPU platform.

Mirrors the reference's test strategy (reference tox.ini: a 2-worker Spark
standalone cluster on one host): multi-device behavior is tested on one host
by splitting the CPU into 8 virtual XLA devices. Must run before jax's
backend initializes — the shared helper raises if it's too late.

This is one of the two ways the program runs (README, Testing): here on the
CPU; on the chip through ``chip_smoke.py`` / ``benchmarks/run.py``, where none
of this applies.
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                os.pardir)))

from tensorflowonspark_tpu.utils.platform_env import force_cpu_platform

force_cpu_platform(8)
# keep subprocesses (LocalEngine executors) on CPU too
os.environ.setdefault("TOS_TPU_TEST_MODE", "1")
# tests keep JAX's persistent compile cache OFF (as before the cache had a
# default place, utils/compile_cache.py): a warm entry turns a test's
# compile into a load, and tests count compiles (xla.compiles, the
# recompile sentinel). Children inherit the setting.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")


def pytest_configure(config):
  config.addinivalue_line(
      "markers",
      "chaos: fault-injection recovery tests (utils.chaos). Part of the "
      "tier-1 'not slow' selection — keep per-test deadlines tight (<10s); "
      "run alone via `make chaos`.")
  config.addinivalue_line(
      "markers", "slow: long-running tests excluded from the tier-1 "
      "selection (`-m 'not slow'`).")
