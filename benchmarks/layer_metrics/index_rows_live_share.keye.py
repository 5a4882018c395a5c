"""model step, serving: percent of the index-key rows the window's decode
steps SCORED that were some live lane's candidates: d ``sparse_rows_candidate``
/ d ``index_rows_read`` (the program's counters, both a layer a step: a live
lane's ``cursor + 1``, and the rows of the index leaf a step's read contracts,
which is the leaf WHOLE, slots x ``max_seq_len``, whatever the cursors).  The
rest lie past the cursors (or in a lane that holds no request) and are masked
after the product: about 100 x 12,500 / 32768 at this cell's cursors.  What an
index read that stopped at each lane's cursor, as the K and V read does, would
leave of the 0.8 GB a step the whole leaf is; a later perf_opt's to move.  A
program without the counters reads nothing."""


from benchmarks.lib import needs_keye_vl2 as needs


def read(report):
  d = needs.counters(report)
  rows = (report.get("stats_delta") or {}).get("index_rows_read")
  if d is None or not rows:
    return None
  return 100.0 * d["sparse_rows_candidate"] / rows
