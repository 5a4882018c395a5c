"""Profiler utilities: step timing, MFU accounting, trace plumbing."""

import time

import numpy as np

import pytest

from tensorflowonspark_tpu.utils import profiler


class TestStepTimer:
  def test_warmup_excluded_and_stats(self):
    t = profiler.StepTimer(warmup=2)
    durations = []
    for i in range(6):
      t0 = time.perf_counter()
      with t.step(items=10):
        time.sleep(0.2 if i < 2 else 0.01)   # slow warmup steps
      durations.append(time.perf_counter() - t0)
    s = t.summary()
    assert s["steps"] == 4
    # relative assertions only — absolute wall-clock bounds flake on
    # loaded CI machines
    warmup_mean = sum(durations[:2]) / 2
    assert s["mean_ms"] / 1e3 < warmup_mean, "warmup steps not excluded"
    assert s["p50_ms"] <= s["p90_ms"] <= s["mean_ms"] * 4
    assert s["items_per_sec"] > 0

  def test_empty_summary(self):
    assert profiler.StepTimer().summary() == {"steps": 0}


class TestMFU:
  def test_resolve_chip_generation(self):
    assert profiler.resolve_chip_generation("v5e") == "v5e"
    assert profiler.resolve_chip_generation("TPU v5 lite") == "v5e"
    assert profiler.resolve_chip_generation("TPU v6 lite") == "v6e"
    assert profiler.resolve_chip_generation("tpu v5p slice") == "v5p"
    assert profiler.resolve_chip_generation("gpu a100") is None
    assert profiler.resolve_chip_generation("") is None

  def test_chip_peak_resolves_v5_lite_to_v5e(self):
    """The device_kind JAX reports on the v5e machine keys the one peak
    table (Google Cloud "TPU v5e": 197 TFLOP/s bf16)."""
    assert profiler.chip_peak_bf16_flops("TPU v5 lite") == ("v5e", 197e12)

  @pytest.mark.parametrize("kind", ["cpu", "TPU v9 mega", ""])
  def test_chip_peak_unknown_device_raises(self, kind):
    """An unknown device is an error, never an assumed peak."""
    with pytest.raises(ValueError, match="unknown device_kind"):
      profiler.chip_peak_bf16_flops(kind)

  def test_peak_table_covers_known_generations(self):
    for g in ("v4", "v5e", "v5p", "v6e"):
      assert profiler.PEAK_BF16_FLOPS[g] > 1e14

  def test_transformer_flops_and_mfu(self):
    # GPT-2-small-class numbers: 124M params, 12 layers, d=768, S=1024
    fpt = profiler.transformer_flops_per_token(124_000_000, 12, 768, 1024)
    assert fpt == 6 * 124e6 + 12 * 12 * 768 * 1024
    # 10k tokens/sec on a v5e => MFU well under 1
    u = profiler.mfu(fpt, 10_000, profiler.PEAK_BF16_FLOPS["v5e"])
    assert 0 < u < 1
    np.testing.assert_allclose(
        u, fpt * 10_000 / 197e12, rtol=1e-9)


class TestTrace:
  def test_trace_writes_profile(self, tmp_path):
    import jax
    import jax.numpy as jnp
    with profiler.trace(str(tmp_path)):
      jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    import os
    found = [f for _, _, fs in os.walk(tmp_path) for f in fs]
    assert found, "trace produced no profile files"

  def test_device_memory_stats_shape(self):
    stats = profiler.device_memory_stats()
    for v in stats.values():
      assert set(v) <= {"bytes_in_use", "peak_bytes_in_use", "bytes_limit"}
