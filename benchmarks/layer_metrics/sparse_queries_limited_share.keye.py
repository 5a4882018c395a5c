"""model step, serving: percent of the window's queries (a token, decode and
prefill together) that had MORE candidates than the selection keeps, so that
it dropped some: (d ``sparse_queries_limited`` + d ``sparse_prefill_limited``)
/ (d ``live_slot_steps`` + d ``sparse_prefill_queries``).  Decode queries are
counted on the device from the live lanes' cursors, prompt tokens on the host
from each chunk's position (real tokens only, no padding).  How much of the
traffic the selection bites: a prompt of 12,800 tokens has 84% of its queries
past 2048 candidates, every decode query of this mix is.  A SANITY reading of
the traffic more than a lever.  A program without the counters reads
nothing."""

from benchmarks.lib import needs_keye_vl2 as needs


def read(report):
  d = needs.counters(report)
  if d is None:
    return None
  total = d["live_slot_steps"] + d["sparse_prefill_queries"]
  return 100.0 * (d["sparse_queries_limited"]
                  + d["sparse_prefill_limited"]) / total
