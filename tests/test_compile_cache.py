"""utils.compile_cache — one placement rule for JAX's persistent cache.

``JAX_COMPILATION_CACHE_DIR`` set: the code sets no directory of its own.
Unset: ONE fixed path inside the checkout, whatever the cwd, the pid or the
time — and the same from a LocalEngine executor, which chdirs into a
mkdtemp directory.
"""

import os
import subprocess
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))

_PRINT_SETUP = (
    "import os\n"
    "from tensorflowonspark_tpu.utils import compile_cache\n"
    "d = compile_cache.setup()\n"
    "import jax\n"
    "print(os.getpid(), d, jax.config.jax_compilation_cache_dir)\n")


def _setup_in_subprocess(cwd, extra_env=None):
  env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
  env.pop("JAX_COMPILATION_CACHE_DIR", None)
  env.update(extra_env or {})
  res = subprocess.run([sys.executable, "-c", _PRINT_SETUP], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=120)
  assert res.returncode == 0, res.stderr
  pid, used, configured = res.stdout.split()
  return int(pid), used, configured


def test_env_set_code_sets_no_directory(monkeypatch, tmp_path):
  """With JAX_COMPILATION_CACHE_DIR set, setup() names that directory and
  never touches jax's cache-dir config (JAX reads the variable itself)."""
  import jax
  from tensorflowonspark_tpu.utils import compile_cache
  monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
  updates = []
  monkeypatch.setattr(jax.config, "update",
                      lambda k, v: updates.append(k))
  assert compile_cache.setup() == str(tmp_path / "outside")
  assert compile_cache.cache_dir() == str(tmp_path / "outside")
  assert "jax_compilation_cache_dir" not in updates
  assert not (tmp_path / "outside").exists()   # nor creates it


def test_env_set_jax_uses_the_variable(tmp_path):
  outside = str(tmp_path / "outside")
  _, used, configured = _setup_in_subprocess(
      REPO, {"JAX_COMPILATION_CACHE_DIR": outside})
  assert used == configured == outside


def test_unset_same_fixed_path_from_two_cwds(tmp_path):
  """Unset: the in-checkout default, resolved from the package's own
  location — not the cwd — and independent of pid and time."""
  from tensorflowonspark_tpu.utils import compile_cache
  pid_a, used_a, conf_a = _setup_in_subprocess(REPO)
  pid_b, used_b, conf_b = _setup_in_subprocess(str(tmp_path))
  assert pid_a != pid_b
  assert used_a == used_b == conf_a == conf_b == compile_cache.DEFAULT_DIR
  assert used_a == os.path.join(REPO, ".jax_cache")
  assert str(pid_a) not in used_a and str(pid_b) not in used_b


def test_default_dir_is_git_ignored():
  with open(os.path.join(REPO, ".gitignore")) as f:
    assert ".jax_cache/" in f.read().split()


def test_unset_same_path_from_a_local_engine_executor(monkeypatch):
  """Executors chdir into a mkdtemp directory; the cache must not follow."""
  from tensorflowonspark_tpu.engine import LocalEngine
  from tensorflowonspark_tpu.utils import compile_cache
  monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

  def where(_it):
    import os as _os
    from tensorflowonspark_tpu.utils import compile_cache as cc
    return [(_os.getcwd(), cc.cache_dir())]

  engine = LocalEngine(num_executors=1)
  try:
    ((cwd, used),) = engine.run_on_executors(where).wait(timeout=60)[0]
  finally:
    engine.stop()
  assert used == compile_cache.DEFAULT_DIR
  assert not used.startswith(cwd) and cwd != REPO


def test_setup_takes_the_callers_stack_out_of_the_cache_key():
  """A Pallas kernel carries its Mosaic module, debug info included, inside
  tpu_custom_call — so with JAX's default full tracebacks the cache key
  depends on who called (seen on the chip: identical program, second
  process, 26 s recompile). setup() turns the caller frames off."""
  res = subprocess.run(
      [sys.executable, "-c",
       "from tensorflowonspark_tpu.utils import compile_cache\n"
       "import jax\n"
       "assert jax.config.jax_include_full_tracebacks_in_locations\n"
       "compile_cache.setup()\n"
       "assert not jax.config.jax_include_full_tracebacks_in_locations\n"],
      env=dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu"),
      capture_output=True, text=True, timeout=120)
  assert res.returncode == 0, res.stderr
