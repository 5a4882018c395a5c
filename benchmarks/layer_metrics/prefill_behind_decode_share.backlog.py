"""serving scheduler and slots: percent of the window's prefill chunk
dispatches made while a decode step of the same pass was unread, d
``prefill_chunks_behind_decode`` / d ``prefill_chunks`` (the program's
counters): the chunks whose host call passed while the chip worked, the rest
were dispatched into a drained device (a pass's second admission, the first
admission of an idle engine).  A program without the counter (the parent of
PR 39) reads nothing."""


def read(report):
  d = report.get("stats_delta") or {}
  if not d.get("prefill_chunks") or "prefill_chunks_behind_decode" not in d:
    return None
  return 100.0 * d["prefill_chunks_behind_decode"] / d["prefill_chunks"]
