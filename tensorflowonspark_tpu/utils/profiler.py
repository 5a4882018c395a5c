"""Deprecated shim: the profiler moved into the observability plane.

``utils/profiler.py`` grew into the measurement plane's training-loop
seam (StepTimer feeds the metrics registry) and now lives at
``tensorflowonspark_tpu.obs.profiler``. This module re-exports the full
old surface so existing imports keep working; new code should import
from ``obs.profiler`` (or use the higher-level ``obs`` plane directly).
"""

import warnings

from tensorflowonspark_tpu.obs.profiler import (  # noqa: F401
    PEAK_BF16_FLOPS,
    StepTimer,
    chip_peak_bf16_flops,
    device_memory_stats,
    mfu,
    resolve_chip_generation,
    start_server,
    trace,
    transformer_flops_per_token,
)

warnings.warn(
    "tensorflowonspark_tpu.utils.profiler moved to "
    "tensorflowonspark_tpu.obs.profiler; this shim will be removed",
    DeprecationWarning, stacklevel=2)
