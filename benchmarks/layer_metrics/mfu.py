"""train loop: end-to-end model FLOP/s utilisation: FLOPs a token needs
(causal count, benchmarks/lib/peaks) x tokens/s of the window over the
chip's published bf16 peak.  Not a kernel's roofline share."""

from benchmarks.lib import peaks


def read(report):
  if "tokens" not in report:
    return None
  shape = report["cell_shape"]
  per_token = peaks.transformer_train_flops_per_token(
      report["n_params"], shape["layers"], shape["d_model"], shape["seq"])
  peak = peaks.chip_peaks(report["device"]["kind"])["bf16_flops"]
  rate = report["tokens"] / report["window_s"]
  return 100.0 * per_token * rate / (peak * report["device"]["count"])
