"""launcher and compile: programs this run found in the persistent
compilation cache (``compile_cache.HitCounter``'s event, counted by the
benchmark's own listener)."""


def read(report):
  return report["compile"]["cache_hits"]
