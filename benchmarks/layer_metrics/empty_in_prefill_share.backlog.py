"""serving scheduler and slots: the part of ``device_empty_share.backlog``
between a request's pop and its row's insert: d(``empty_admit_s`` +
``empty_prefill_s`` + ``empty_prefill_sync_s`` + ``empty_insert_s``) /
window (the program's counters): a prompt's first chunk and the insert,
dispatched with nothing queued."""

from benchmarks.lib import empty


def read(report):
  return empty.empty_share(report, empty.PREFILL_KEYS)
