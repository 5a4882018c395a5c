"""serving scheduler and slots: percent of the tokens the window's prefill
chunks computed that were a padded tail's padding, d ``prefill_padded_tokens``
/ d ``prefill_tokens`` (the program's counters): what one program a prompt
costs the device.  0 for a model that keeps the exact plan.  A program without
the counters (the parent of PR 27) reads nothing."""


def read(report):
  d = report.get("stats_delta") or {}
  if not d.get("prefill_tokens") or "prefill_padded_tokens" not in d:
    return None
  return 100.0 * d["prefill_padded_tokens"] / d["prefill_tokens"]
