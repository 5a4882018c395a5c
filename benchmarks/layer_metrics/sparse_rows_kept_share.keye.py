"""model step, serving: percent of a decode query's candidate rows that its
selection KEPT, over the window's decode steps and layers: d
``sparse_rows_kept`` / d ``sparse_rows_candidate`` (the program's counters:
the entries of each live lane's keep row, the own token among them, summed
over the six layers; its candidates are the cache's rows below its cursor and
its own token).  About 100 x 2048 / 13,000 at this cell's cursors.  The decode
step reads every candidate's keys and values (the kernel under the keep rows
brings whole blocks: rows chosen by a seeded indexer lie in every block); this
is the share of those bytes that a read of the CHOSEN rows alone would still
bring (``needs_keye_vl2.chosen_rows_bytes`` adds the index keys of every
candidate): the size of a later perf_opt's prize.  ``better: lower`` says the
selection bites harder, not that a program should move it: it is a property
of the traffic's cursors.  A program without the counters reads nothing."""

from benchmarks.lib import needs_keye_vl2 as needs


def read(report):
  d = needs.counters(report)
  if d is None or not d["sparse_rows_candidate"]:
    return None
  return 100.0 * d["sparse_rows_kept"] / d["sparse_rows_candidate"]
