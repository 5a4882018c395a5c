"""model step, serving: percent of its lanes' positions that a WINDOW layer
no longer reads or holds: 100 x (1 - d ``window_context_tokens`` / d
``live_context_tokens``), as ``window_rows_saved_share.trinity`` reads it
(``window_context_tokens`` sums ``min(cursor, 128)``: the rows one window
layer has to read for a step; ``live_context_tokens`` sums the cursors: what a
full layer reads, and what a window layer held as a whole-context leaf
would).  With a window of 128 under contexts of thousands it reads in the
high nineties.  A program without the counters reads nothing."""

from benchmarks.lib import needs_mimo_v2_flash as needs


def read(report):
  d = needs.counters(report)
  if d is None or not d["live_context_tokens"]:
    return None
  return 100.0 * (1.0 - d["window_context_tokens"] / d["live_context_tokens"])
