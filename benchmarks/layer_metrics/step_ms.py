"""train loop: median over dispatches of the time from one dispatch's loss
fetch to the next one's, over the K steps a dispatch fuses."""

import statistics


def read(report):
  ends = report.get("dispatch_ends")
  if not ends or len(ends) < 3:
    return None
  gaps = [b - a for a, b in zip(ends, ends[1:])]
  return 1e3 * statistics.median(gaps) / report["cell_shape"]["unroll"]
