"""model step, serving: percent of the window's calls of slab-returning
programs (insert, the fused decode dispatch) after which the slab that went
in was deleted, d ``slab_in_place`` / d ``slab_dispatches`` (the program's
counters): the program took the donated slab over and updated it in place.
Under 100 a program copied the whole KV slab at its edge (JAX only warns
when a donation cannot be used).  A program without the counters (the
parent of PR 25) reads nothing."""


def read(report):
  d = report.get("stats_delta") or {}
  if not d.get("slab_dispatches") or "slab_in_place" not in d:
    return None
  return 100.0 * d["slab_in_place"] / d["slab_dispatches"]
