"""Tests for utils.platform_env — the shared CPU-platform sanitizer.

These run in subprocesses because the helpers mutate process-global jax
config/env state that the test process itself already fixed up (conftest).
"""

import os
import subprocess
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


def _run(code, extra_env=None):
  env = dict(os.environ)
  env.pop("JAX_PLATFORMS", None)
  env.pop("XLA_FLAGS", None)
  env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
  env.update(extra_env or {})
  return subprocess.run(
      [sys.executable, "-c", code], env=env, timeout=120,
      capture_output=True, text=True)


def test_force_cpu_platform_device_count():
  res = _run(
      "from tensorflowonspark_tpu.utils.platform_env import force_cpu_platform\n"
      "force_cpu_platform(6)\n"
      "import jax\n"
      "print(jax.default_backend(), jax.device_count())\n")
  assert res.returncode == 0, res.stderr
  assert res.stdout.split() == ["cpu", "6"]


def test_force_cpu_platform_preserves_larger_count():
  res = _run(
      "import os\n"
      "from tensorflowonspark_tpu.utils.platform_env import force_cpu_platform\n"
      "force_cpu_platform(4)\n"
      "print(os.environ['XLA_FLAGS'])\n",
      extra_env={"XLA_FLAGS": "--xla_force_host_platform_device_count=16"})
  assert res.returncode == 0, res.stderr
  assert "--xla_force_host_platform_device_count=16" in res.stdout


def test_force_cpu_platform_grows_smaller_count():
  res = _run(
      "import os\n"
      "from tensorflowonspark_tpu.utils.platform_env import force_cpu_platform\n"
      "force_cpu_platform(8)\n"
      "print(os.environ['XLA_FLAGS'])\n",
      extra_env={"XLA_FLAGS": "--xla_force_host_platform_device_count=2 "
                              "--xla_cpu_enable_fast_math=false"})
  assert res.returncode == 0, res.stderr
  assert "--xla_force_host_platform_device_count=8" in res.stdout
  assert "--xla_cpu_enable_fast_math=false" in res.stdout


def test_force_cpu_platform_too_late_raises():
  res = _run(
      "import jax\n"
      "jax.devices()\n"
      "from tensorflowonspark_tpu.utils.platform_env import force_cpu_platform\n"
      "try:\n"
      "  force_cpu_platform(64)\n"
      "except RuntimeError as e:\n"
      "  print('RAISED', e)\n",
      extra_env={"JAX_PLATFORMS": "cpu"})
  assert res.returncode == 0, res.stderr
  assert "RAISED" in res.stdout


def test_importing_the_package_initialises_no_backend():
  """One process per chip: the driver imports the orchestration layer (and
  chip_smoke's parent imports cluster/engine/serving) without ever taking
  the device — a module-level jnp constant would initialise the backend in
  a process that must stay off it."""
  res = _run(
      "import tensorflowonspark_tpu\n"
      "import tensorflowonspark_tpu.cluster, tensorflowonspark_tpu.engine\n"
      "import tensorflowonspark_tpu.serving, tensorflowonspark_tpu.node\n"
      "import tensorflowonspark_tpu.models.transformer\n"
      "import tensorflowonspark_tpu.serving.slots, tensorflowonspark_tpu.ops\n"
      "import tensorflowonspark_tpu.parallel.sharding\n"
      "from tensorflowonspark_tpu.utils.platform_env import "
      "backend_initialized\n"
      "print('UP' if backend_initialized() else 'DOWN')\n",
      extra_env={"JAX_PLATFORMS": "cpu"})
  assert res.returncode == 0, res.stderr
  assert res.stdout.split() == ["DOWN"]
