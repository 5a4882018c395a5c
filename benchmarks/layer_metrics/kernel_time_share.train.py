"""kernels: share of the device's busy time spent inside Mosaic (Pallas)
kernels: flash attention forward and backward, fused LayerNorm."""


def read(report):
  summary = report.get("trace_summary")
  if not summary or not summary.get("kernels"):
    return None
  kernel_s = sum(k["seconds"] for k in summary["kernels"].values())
  return 100.0 * kernel_s / summary["busy_s"]
