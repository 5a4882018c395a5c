"""kernels: the flash-attention FORWARD kernel's share of its roofline: the
least time the chip could take for the calls traced (the larger of FLOPs over
the bf16 peak and bytes over the HBM peak, benchmarks/lib/peaks) over the
device time of its events.  The trace tells the forward kernel apart by the
name Mosaic gives it today, the Python function's: ``%_fwd_impl``."""

from benchmarks.lib import peaks

KERNEL = "%_fwd_impl"


def read(report):
  summary = report.get("trace_summary")
  shape = report.get("cell_shape")
  k = (summary or {}).get("kernels", {}).get(KERNEL)
  if not k or not shape or "heads" not in shape or not k["seconds"] > 0:
    return None
  flops, nbytes = peaks.flash_forward_flops_bytes(
      shape["batch"], shape["seq"], shape["heads"],
      shape["d_model"] // shape["heads"], causal=True)
  least, _ = peaks.roofline_seconds(flops, nbytes, report["device"]["kind"])
  return 100.0 * least * k["calls"] / k["seconds"]
