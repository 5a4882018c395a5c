"""model step, serving: percent of its lanes' positions that a WINDOW layer
no longer reads or holds: 100 x (1 - d ``window_context_tokens`` / d
``live_context_tokens``), the program's counters over live lanes
(``window_context_tokens`` sums ``min(cursor, window)``: the rows one window
layer has to read for a step; ``live_context_tokens`` sums the cursors: what
the full layer reads, and what a window layer held as a whole-context leaf
would).  0 while every lane is inside its window.  A program without the
counters reads nothing."""

from benchmarks.lib import needs_trinity as needs


def read(report):
  d = needs.counters(report)
  if d is None or not d["live_context_tokens"]:
    return None
  return 100.0 * (1.0 - d["window_context_tokens"] / d["live_context_tokens"])
