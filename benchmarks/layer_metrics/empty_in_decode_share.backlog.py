"""serving scheduler and slots: the part of ``device_empty_share.backlog``
round a decode pass: d(``empty_reap_s`` + ``empty_decode_prep_s`` +
``empty_decode_dispatch_s`` + ``empty_decode_fetch_s`` +
``empty_decode_harvest_s``) / window (the program's counters): the token
matrix read, harvested and the next step dispatched, with nothing queued.
With ``empty_in_prefill_share.backlog`` (and ``empty_idle_s``, about nothing
under a backlog) it sums to ``device_empty_share.backlog``."""

from benchmarks.lib import empty


def read(report):
  return empty.empty_share(report, empty.DECODE_KEYS)
