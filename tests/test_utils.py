"""L0' unit tests: hostinfo, tpu_info discovery/allocation matrix, paths.

Port of the reference's policy-matrix style (reference
tests/test_TFSparkNode.py:49-190 for GPU allocation, tests/test_TFNode.py:7-25
for hdfs_path) onto the TPU modules.
"""

import os

import pytest

from tensorflowonspark_tpu.utils import hostinfo, paths, tpu_info


class TestHostinfo:
  def test_get_ip_address(self):
    ip = hostinfo.get_ip_address()
    assert isinstance(ip, str) and ip.count(".") == 3

  def test_get_free_port(self):
    p = hostinfo.get_free_port()
    assert 0 < p < 65536

  def test_find_in_path(self, tmp_path):
    f = tmp_path / "present.txt"
    f.write_text("x")
    path = os.pathsep.join(["/nonexistent", str(tmp_path)])
    assert hostinfo.find_in_path(path, "present.txt") == str(f)
    assert hostinfo.find_in_path(path, "absent.txt") is False

  def test_executor_id_roundtrip(self, tmp_path):
    hostinfo.write_executor_id(7, str(tmp_path))
    assert hostinfo.read_executor_id(str(tmp_path)) == 7

  def test_executor_id_missing(self, tmp_path):
    with pytest.raises(RuntimeError, match="No executor_id"):
      hostinfo.read_executor_id(str(tmp_path))


class TestPaths:
  """Parity matrix: reference tests/test_TFNode.py hdfs_path tests."""

  def test_absolute_schemes_passthrough(self):
    for p in ["gs://bucket/x", "hdfs://nn:8020/x", "file:///tmp/x",
              "viewfs://ns/x", "s3a://b/x"]:
      assert paths.absolute_path(p, "hdfs://nn:8020") == p

  def test_absolute_local(self):
    assert paths.absolute_path("/tmp/x", "file://") == "file:///tmp/x"

  def test_absolute_on_default_fs(self):
    assert paths.absolute_path("/data/x", "gs://bucket") == "gs://bucket/data/x"

  def test_relative_local(self):
    got = paths.absolute_path("rel/x", "file://", working_dir="/work")
    assert got == "file:///work/rel/x"

  def test_relative_remote(self):
    assert paths.absolute_path("rel/x", "gs://bucket") == "gs://bucket/rel/x"

  def test_strip_scheme(self):
    assert paths.strip_scheme("file:///tmp/x") == "/tmp/x"
    assert paths.strip_scheme("/tmp/x") == "/tmp/x"

  def test_is_remote_uri(self):
    assert paths.is_remote_uri("gs://bucket/x")
    assert paths.is_remote_uri("s3://bucket/x")
    assert not paths.is_remote_uri("file:///tmp/x")
    assert not paths.is_remote_uri("/tmp/x")
    assert not paths.is_remote_uri("rel/x")

  def test_for_io_remote_untouched(self):
    assert paths.for_io("gs://bucket/dir") == "gs://bucket/dir"
    assert paths.for_io("hdfs://nn:8020/dir") == "hdfs://nn:8020/dir"

  def test_for_io_local_absolute(self, tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    assert paths.for_io("rel/dir") == str(tmp_path / "rel" / "dir")
    assert paths.for_io("file:///tmp/x") == "/tmp/x"

  def test_join_scheme_aware(self):
    assert paths.join("gs://bucket/dir", "model") == "gs://bucket/dir/model"
    assert paths.join("gs://bucket/dir/", "a", "b") == "gs://bucket/dir/a/b"
    assert paths.join("/tmp/dir", "model") == "/tmp/dir/model"


class TestTPUInfo:
  """Mocked discovery/allocation matrix (no real TPU needed)."""

  def test_parse_v5e(self):
    topo = tpu_info.parse_accelerator_type("v5litepod-16")
    assert topo.num_chips == 16
    assert topo.chips_per_host == 8
    assert topo.num_hosts == 2
    assert topo.num_devices == 16

  def test_parse_v3(self):
    topo = tpu_info.parse_accelerator_type("v3-32")
    # v3-32 = 32 cores = 16 chips, 4 chips/host; 2 JAX devices per chip
    assert topo.num_chips == 16
    assert topo.cores_per_chip == 2
    assert topo.num_hosts == 4
    assert topo.num_devices == 32

  def test_parse_v4_counts_cores_not_chips(self):
    # v4-8 = 8 TensorCores = 4 megacore chips on ONE host, 4 JAX devices
    topo = tpu_info.parse_accelerator_type("v4-8")
    assert topo.num_chips == 4
    assert topo.num_hosts == 1
    assert topo.num_devices == 4

  def test_parse_v5p_counts_cores(self):
    topo = tpu_info.parse_accelerator_type("v5p-8")
    assert topo.num_chips == 4
    assert topo.num_hosts == 1
    assert topo.num_devices == 4

  def test_parse_invalid(self):
    with pytest.raises(ValueError):
      tpu_info.parse_accelerator_type("gpu-a100")

  def test_from_env(self):
    env = {"TPU_ACCELERATOR_TYPE": "v5litepod-8",
           "TPU_WORKER_HOSTNAMES": "h0,h1"}
    topo = tpu_info.from_env(env)
    assert topo.num_chips == 8
    assert topo.hostnames == ["h0", "h1"]
    assert topo.num_hosts == 2

  def test_from_env_absent(self):
    assert tpu_info.from_env({}) is None

  def test_chip_env_single_worker(self):
    env = tpu_info.chip_env_for_worker(4, worker_index=0, workers_per_host=1)
    assert env["TPU_VISIBLE_CHIPS"] == "0,1,2,3"
    assert env["CLOUD_TPU_TASK_ID"] == "0"

  def test_chip_env_multi_worker_disjoint(self):
    e0 = tpu_info.chip_env_for_worker(2, worker_index=0, workers_per_host=4)
    e3 = tpu_info.chip_env_for_worker(2, worker_index=3, workers_per_host=4)
    assert e0["TPU_VISIBLE_CHIPS"] == "0,1"
    assert e3["TPU_VISIBLE_CHIPS"] == "6,7"
    assert e0["TPU_PROCESS_PORT"] != e3["TPU_PROCESS_PORT"]

  def test_chip_env_multihost_worker_index_wraps(self):
    # worker 5 of a 2-worker-per-host layout lands on local slot 1
    env = tpu_info.chip_env_for_worker(4, worker_index=5, workers_per_host=2)
    assert env["TPU_VISIBLE_CHIPS"] == "4,5,6,7"
    assert env["CLOUD_TPU_TASK_ID"] == "1"

  def test_chip_env_overflow_raises(self):
    with pytest.raises(ValueError, match="at most"):
      tpu_info.chip_env_for_worker(4, worker_index=3, workers_per_host=4)

  def test_chip_env_invalid(self):
    with pytest.raises(ValueError):
      tpu_info.chip_env_for_worker(0, 0, 1)

  def test_chip_env_bounds_tile_v5e_grid(self):
    """2 workers x 4 chips on a v5e host (2x4 grid): per-process bounds
    2,2,1 with process bounds 1,2,1 — not a bogus 1x8 arrangement that
    libtpu would reject."""
    env = tpu_info.chip_env_for_worker(4, worker_index=1, workers_per_host=2,
                                       generation="v5e")
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "2,2,1"
    assert env["TPU_PROCESS_BOUNDS"] == "1,2,1"

  def test_chip_env_bounds_tile_v4_grid(self):
    # 2 workers x 2 chips on a v4 host (2x2 grid)
    env = tpu_info.chip_env_for_worker(2, worker_index=0, workers_per_host=2,
                                       generation="v4")
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "2,1,1"
    assert env["TPU_PROCESS_BOUNDS"] == "1,2,1"

  def test_chip_env_full_host_single_process(self):
    env = tpu_info.chip_env_for_worker(8, worker_index=0, workers_per_host=1,
                                       generation="v5e")
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "2,4,1"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"

  def test_chip_env_one_chip_per_worker_covers_grid(self):
    env = tpu_info.chip_env_for_worker(1, worker_index=3, workers_per_host=8,
                                       generation="v6e")
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_BOUNDS"] == "2,4,1"

  def test_chip_env_untileable_raises(self):
    with pytest.raises(ValueError, match="cannot tile"):
      tpu_info.chip_env_for_worker(3, worker_index=0, workers_per_host=1,
                                   generation="v5e")

  # -- discovery bounded by the chips the host really shows ------------------

  def test_from_env_bounded_by_device_nodes(self, monkeypatch):
    """The one-chip v5e machine says v5litepod-4 / 2,2,1 and shows ONE
    /dev/vfio/<n>: allocating over four would wait for three peers."""
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    monkeypatch.setattr(tpu_info, "local_chip_count", lambda: 1)
    topo = tpu_info.from_env()
    assert (topo.chips_per_host, topo.num_chips, topo.host_grid) == \
        (1, 1, None)
    monkeypatch.setattr(tpu_info, "local_chip_count", lambda: 4)
    topo = tpu_info.from_env()
    assert (topo.chips_per_host, topo.host_grid) == (4, (2, 2))

  def test_from_device_nodes_when_env_is_silent(self, monkeypatch):
    monkeypatch.delenv("TPU_ACCELERATOR_TYPE", raising=False)
    monkeypatch.setattr(tpu_info, "local_chip_count", lambda: 4)
    monkeypatch.setattr(tpu_info, "_pci_generation", lambda: "v5e")
    topo = tpu_info.from_env()
    assert (topo.generation, topo.chips_per_host, topo.num_hosts) == \
        ("v5e", 4, 1)
    # an explicit environ stays pure (no host probing) for the mocks above
    assert tpu_info.from_env({}) is None

  def test_claim_chips_without_topology_raises_outside_test_mode(
      self, monkeypatch):
    """chips_per_node > 0 with no topology is an ERROR, not a skip: every
    co-hosted process would otherwise take all the host's chips."""
    monkeypatch.delenv("TPU_ACCELERATOR_TYPE", raising=False)
    monkeypatch.setattr(tpu_info, "local_chip_count", lambda: 0)
    assert tpu_info.claim_chips(0, 0) is None          # nothing asked
    assert tpu_info.claim_chips(1, 0) is None          # test mode (conftest)
    monkeypatch.delenv("TOS_TPU_TEST_MODE")
    with pytest.raises(RuntimeError, match="no TPU topology is visible"):
      tpu_info.claim_chips(1, 0, what="executor 0")

  def test_claim_chips_more_workers_than_chips_raises(self, monkeypatch):
    monkeypatch.delenv("TOS_TPU_TEST_MODE")
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    monkeypatch.setattr(tpu_info, "local_chip_count", lambda: 1)
    applied = []
    monkeypatch.setattr(tpu_info, "apply_chip_env", applied.append)
    with pytest.raises(RuntimeError, match="must not exceed the chips"):
      tpu_info.claim_chips(1, 0, workers_on_host=4)
    env = tpu_info.claim_chips(1, 0, workers_on_host=1)
    assert applied == [env]
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_ADDRESSES"].count(",") == 0

  def test_claim_chips_sizes_the_grid_by_the_cohosted_population(
      self, monkeypatch):
    """Two executors on a four-chip host form a 2-process slice; sizing it
    by capacity (four) would wait for two peers that never come."""
    monkeypatch.delenv("TOS_TPU_TEST_MODE")
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    monkeypatch.setattr(tpu_info, "local_chip_count", lambda: 4)
    monkeypatch.setattr(tpu_info, "apply_chip_env", lambda env: None)
    env = tpu_info.claim_chips(1, 1, workers_on_host=2)
    assert env["TPU_VISIBLE_CHIPS"] == "1"
    assert env["TPU_PROCESS_BOUNDS"] == "2,1,1"
    assert env["TPU_PROCESS_ADDRESSES"].count(",") == 1
