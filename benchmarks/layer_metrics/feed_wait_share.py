"""feed plane: share of the window the node's loop thread spent blocked in
``next()`` on ``device_prefetch(slab_batches(feed))``."""


def read(report):
  if "feed_wait_s" not in report:
    return None
  return 100.0 * report["feed_wait_s"] / report["window_s"]
