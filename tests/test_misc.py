"""Cross-cutting coverage: the inference CLI as a real subprocess, bundle
export/import round-trips, rendezvous protocol verbs, and small API
surfaces not covered elsewhere."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest


class TestTensorboardDiscovery:
  """Parity: the reference's three-step search and spawn
  (TFSparkNode.py:292-329)."""

  def test_finds_executable(self, tmp_path):
    from tensorflowonspark_tpu import node
    (tmp_path / "tensorboard").write_text("# fake executable")
    assert node._find_tensorboard(str(tmp_path)) == \
        str(tmp_path / "tensorboard")

  def test_falls_back_to_module_main(self, tmp_path):
    from tensorflowonspark_tpu import node
    pkg = tmp_path / "tensorboard"
    pkg.mkdir()
    (pkg / "main.py").write_text("# fake module entry")
    assert node._find_tensorboard(str(tmp_path)) == str(pkg / "main.py")

  def test_executable_takes_precedence(self, tmp_path):
    from tensorflowonspark_tpu import node
    d1, d2 = tmp_path / "a", tmp_path / "b"
    d1.mkdir(), d2.mkdir()
    (d2 / "tensorboard").write_text("# exe")
    pkg = d1 / "tensorboard"
    pkg.mkdir()
    (pkg / "main.py").write_text("# module")
    search = os.pathsep.join([str(d1), str(d2)])
    assert node._find_tensorboard(search) == str(d2 / "tensorboard")

  def test_default_search_covers_pythonpath(self, tmp_path, monkeypatch):
    from tensorflowonspark_tpu import node
    pkg = tmp_path / "tensorboard"
    pkg.mkdir()
    (pkg / "main.py").write_text("# via PYTHONPATH")
    monkeypatch.setenv("PATH", str(tmp_path / "nothing_here"))
    monkeypatch.setenv("PYTHONPATH", str(tmp_path))
    found = node._find_tensorboard()
    # the default search also covers the interpreter's bin dir and
    # sys.path, and some images ship a tensorboard launcher there — any
    # hit (executable or module-form main.py) proves the default search
    # string includes the env-derived entries
    assert found
    assert str(found).endswith(os.path.join("tensorboard", "main.py")) \
        or os.path.basename(str(found)) == "tensorboard"

  def test_not_found_returns_false(self, tmp_path):
    from tensorflowonspark_tpu import node
    assert not node._find_tensorboard(str(tmp_path))


class TestSpawnTensorboard:
  def test_spawn_args_and_url(self, tmp_path, monkeypatch):
    from tensorflowonspark_tpu import node
    fake = tmp_path / "tensorboard"
    fake.write_text("# fake")
    monkeypatch.setenv("TENSORBOARD_PORT", "23456")
    monkeypatch.setattr(node, "_find_tensorboard", lambda: str(fake))
    calls = {}

    class _Proc:
      pid = 4242

    monkeypatch.setattr(
        node.subprocess, "Popen",
        lambda args, **kw: calls.setdefault("args", args) and _Proc()
        or _Proc())
    info = node._spawn_tensorboard(str(tmp_path / "logs"))
    assert info["pid"] == 4242
    assert info["url"].startswith("http://") and info["url"].endswith(":23456")
    args = calls["args"]
    assert args[0] == sys.executable and args[1] == str(fake)
    assert "--logdir" in args and str(tmp_path / "logs") in args
    assert "--port" in args and "23456" in args

  def test_returns_none_when_not_found(self, monkeypatch):
    from tensorflowonspark_tpu import node
    monkeypatch.setattr(node, "_find_tensorboard", lambda: False)
    assert node._spawn_tensorboard("/tmp/logs") is None


class TestInferenceCLISubprocess:
  def test_python_dash_m_invocation(self, tmp_path):
    """The documented `python -m tensorflowonspark_tpu.inference_cli`
    entry point, as a real subprocess."""
    from tensorflowonspark_tpu import pipeline
    from tensorflowonspark_tpu.data import dfutil
    from tensorflowonspark_tpu.data.schema import parse_schema

    def predict_fn(params, batch):
      return {"y": np.asarray(batch["x"], "float32") * params["m"]}

    export_dir = str(tmp_path / "model")
    pipeline.export_bundle({"m": np.float32(10.0)}, predict_fn, export_dir)
    dfutil.save_as_tfrecords([[(1.5,), (2.5,)]],
                             parse_schema("struct<v:float>"),
                             str(tmp_path / "data"))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out_path = str(tmp_path / "preds.jsonl")
    proc = subprocess.run(
        [sys.executable, "-m", "tensorflowonspark_tpu.inference_cli",
         "--export_dir", export_dir,
         "--input", str(tmp_path / "data"),
         "--schema_hint", "struct<v:float>",
         "--input_mapping", json.dumps({"v": "x"}),
         "--output_mapping", json.dumps({"y": "pred"}),
         "--output", out_path],
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stderr[-2000:]
    preds = [json.loads(l)["pred"] for l in open(out_path)]
    assert preds == [15.0, 25.0]

  def test_mapping_free_cli_uses_bundle_signature(self, tmp_path):
    """Without --output_mapping the CLI derives output columns from the
    signature recorded at export (transformSchema parity,
    reference TFModel.scala:294-311)."""
    from tensorflowonspark_tpu import pipeline
    from tensorflowonspark_tpu.data import dfutil
    from tensorflowonspark_tpu.data.schema import parse_schema

    def predict_fn(params, batch):
      x = np.asarray(batch["v"], "float32")
      return {"doubled": x * params["k"], "negated": -x}

    export_dir = str(tmp_path / "model")
    pipeline.export_bundle(
        {"k": np.float32(2.0)}, predict_fn, export_dir,
        example_batch={"v": np.zeros((1,), "float32")})
    dfutil.save_as_tfrecords([[(3.0,), (4.0,)]],
                             parse_schema("struct<v:float>"),
                             str(tmp_path / "data"))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out_path = str(tmp_path / "preds.jsonl")
    proc = subprocess.run(
        [sys.executable, "-m", "tensorflowonspark_tpu.inference_cli",
         "--export_dir", export_dir,
         "--input", str(tmp_path / "data"),
         "--schema_hint", "struct<v:float>",
         "--output", out_path],
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = [json.loads(l) for l in open(out_path)]
    assert rows == [{"doubled": 6.0, "negated": -3.0},
                    {"doubled": 8.0, "negated": -4.0}]


class TestCompatRoundtrip:
  def test_export_import_model(self, tmp_path):
    import jax.numpy as jnp
    from tensorflowonspark_tpu.utils import compat

    state = {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.asarray(1.5)}
    target = compat.export_model(state, str(tmp_path / "exp"),
                                 is_chief=True)
    assert target == str(tmp_path / "exp")
    restored = compat.import_model(target)
    np.testing.assert_allclose(np.asarray(restored["w"]),
                               np.arange(6.0).reshape(2, 3))

  def test_non_chief_writes_elsewhere(self, tmp_path):
    import jax.numpy as jnp
    from tensorflowonspark_tpu.utils import compat

    target = compat.export_model({"w": jnp.zeros(2)},
                                 str(tmp_path / "exp2"), is_chief=False)
    try:
      assert target != str(tmp_path / "exp2")
      assert not os.path.exists(str(tmp_path / "exp2"))
    finally:
      import shutil
      shutil.rmtree(target, ignore_errors=True)


class TestRendezvousVerbs:
  def test_qinfo_and_list(self):
    from tensorflowonspark_tpu.control.rendezvous import Client, Server

    s = Server(3)
    addr = s.start()
    try:
      c = Client(addr)
      c.register({"executor_id": 0, "host": "h0"})
      c.register({"executor_id": 2, "host": "h2"})
      count = c._request({"type": "QINFO"})
      assert count["registered"] == 2 and count["required"] == 3
      listed = c.get_reservations()
      assert [m["executor_id"] for m in listed] == [0, 2]
      unknown = c._request({"type": "NOPE"})
      assert unknown["type"] == "ERROR"
      c.close()
    finally:
      s.stop()


class TestSmallSurfaces:
  def test_yield_batch_scalar_rows(self):
    from tensorflowonspark_tpu.pipeline import yield_batch
    batches = list(yield_batch([1, 2, 3, 4, 5], batch_size=2))
    assert batches == [[[1, 2]], [[3, 4]], [[5]]]

  def test_yield_batch_multi_tensor(self):
    from tensorflowonspark_tpu.pipeline import yield_batch
    rows = [(1, "a"), (2, "b"), (3, "c")]
    batches = list(yield_batch(rows, batch_size=2, num_tensors=2))
    assert batches == [[[1, 2], ["a", "b"]], [[3], ["c"]]]

  def test_namespace_rejects_garbage(self):
    from tensorflowonspark_tpu.pipeline import Namespace
    with pytest.raises(TypeError):
      Namespace(42)

  def test_batched_custom_collate(self):
    from tensorflowonspark_tpu.data import readers
    got = list(readers.batched([1, 2, 3, 4], 2,
                               collate=lambda rows: sum(rows)))
    assert got == [3, 7]

  def test_datafeed_arrays_without_mapping(self):
    from tensorflowonspark_tpu.control import feedhub
    from tensorflowonspark_tpu.datafeed import DataFeed
    hub = feedhub.start(b"k", ["input", "output", "error"], mode="local")
    try:
      hub.get_queue("input").put_many([1.0, 2.0, None])
      feed = DataFeed(hub)
      arr = feed.next_batch_arrays(5, dtype="float32")
      np.testing.assert_allclose(arr, [1.0, 2.0])
    finally:
      hub.shutdown()

  def test_engine_factory(self):
    from tensorflowonspark_tpu.engine import get_engine
    e = get_engine("local", num_executors=1)
    assert e.num_executors == 1
    e.stop()
    with pytest.raises(ValueError):
      get_engine("nope")


class TestOpsScripts:
  def test_shell_scripts_parse(self):
    """Every ops recipe in scripts/ must at least pass bash -n (they
    cannot run here — no gcloud/Spark — but they must not rot)."""
    import glob
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    scripts = glob.glob(os.path.join(repo, "scripts", "*.sh"))
    assert len(scripts) >= 5, scripts
    for s in scripts:
      res = subprocess.run(["bash", "-n", s], capture_output=True,
                           text=True)
      assert res.returncode == 0, "%s: %s" % (s, res.stderr)

  def test_submit_train_runs_with_no_executor_env_to_pass(self, tmp_path):
    """scripts/submit_train.sh under its own ``set -u`` with neither
    control-plane pin set: the executor-env list is empty, and the recipe
    still reaches spark-submit with the app and the cluster size."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spark = tmp_path / "bin" / "spark-submit"
    spark.parent.mkdir()
    spark.write_text("#!/bin/bash\nprintf '%s\\n' \"$@\"\n")
    spark.chmod(0o755)
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("TOS_TPU_SERVER_", "EXTRA_SPARK"))}
    env.update(SPARK_HOME=str(tmp_path), MASTER="spark://here:7077")
    res = subprocess.run(
        ["bash", os.path.join(repo, "scripts", "submit_train.sh"), "app.py",
         "--steps", "3"], capture_output=True, text=True, env=env, timeout=60)
    assert res.returncode == 0, res.stderr
    args = res.stdout.split("\n")
    assert "app.py" in args and "--cluster_size" in args and "3" in args
    assert not [a for a in args if "executorEnv" in a]
