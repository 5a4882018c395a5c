"""Published peaks of the chips this benchmark may run on, and the functions
that count the operations and bytes an algorithm NEEDS (not what a program
happens to execute).  A device that is not in the table is an error.

Copied from ``tensorflowonspark_tpu/obs/profiler.py`` (``PEAK_BF16_FLOPS``,
``chip_peak_bf16_flops``, ``transformer_flops_per_token``) and
``tools/roofline.py`` (HBM bandwidth, flash byte/FLOP formulas), so that a
later change to the program cannot move the yardstick.
"""

#: key: substring of ``device_kind`` as JAX reports it (lower case).
#: Source: Google Cloud documentation, "TPU v5e" system architecture page:
#: 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.
PEAKS = {
    "v5 lite": dict(chip="v5e", bf16_flops=197e12, hbm_bytes_per_s=819e9,
                    hbm_bytes=16e9,
                    source="Google Cloud documentation, TPU v5e"),
    "v5e": dict(chip="v5e", bf16_flops=197e12, hbm_bytes_per_s=819e9,
                hbm_bytes=16e9,
                source="Google Cloud documentation, TPU v5e"),
}


def chip_peaks(device_kind: str) -> dict:
  """The peak row for a ``device_kind``; an unknown device raises."""
  text = (device_kind or "").lower()
  for key, row in PEAKS.items():
    if key in text:
      return row
  raise ValueError(
      "unknown device_kind %r: no row in benchmarks/lib/peaks.PEAKS (%s); "
      "add the chip with its published peaks, do not assume one"
      % (device_kind, ", ".join(sorted(PEAKS))))


def transformer_train_flops_per_token(n_params: int, num_layers: int,
                                      d_model: int, seq_len: int,
                                      causal: bool = True) -> float:
  """Training FLOPs a token NEEDS: ``6 N`` for the forward and backward
  matrix multiplications (the tied table counts once: it is the head) plus
  attention's score and value products, ``12 L d S`` when every position
  attends to every other (PaLM's accounting) and half of that under a causal
  mask, where the upper triangle is never needed."""
  attn = 12.0 * num_layers * d_model * seq_len
  return 6.0 * n_params + (attn / 2.0 if causal else attn)


def flash_forward_flops_bytes(batch: int, seq: int, heads: int,
                              head_dim: int, causal: bool = True,
                              dtype_bytes: int = 2):
  """(FLOPs, HBM bytes) one flash-attention FORWARD call needs: QK^T and PV
  are ``2 * 2 * B * H * S^2 * hd`` (half under a causal mask); q, k, v read
  once and the output written once."""
  flops = 4.0 * batch * heads * seq * seq * head_dim
  if causal:
    flops /= 2.0
  nbytes = 4.0 * batch * seq * heads * head_dim * dtype_bytes
  return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, device_kind: str):
  """The least time the chip could take, and which roof bounds it."""
  p = chip_peaks(device_kind)
  tc, tm = flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"]
  return (tc, "compute") if tc >= tm else (tm, "memory")
