"""The arithmetic of ``correct``: every number compared has its own limit."""

import math
import statistics


def check(name: str, value, limit, better: str = "le") -> dict:
  """One compared number beside its limit. ``le``: value <= limit passes;
  ``ge``: value >= limit; ``eq``: exact."""
  if value is None or (isinstance(value, float) and math.isnan(value)):
    ok = False
  elif better == "le":
    ok = value <= limit
  elif better == "ge":
    ok = value >= limit
  else:
    ok = value == limit
  return dict(name=name, value=value, limit=limit, rule=better, ok=bool(ok))


def worst_leaf_gap(program: dict, reference: dict) -> float:
  """Largest gap between the program's norm of a leaf and the reference's
  (a gap of norms, not the norm of a difference), against the reference's
  norm of that leaf or of the median leaf, whichever is larger: some
  gradients are all but zero."""
  if set(program) != set(reference):
    raise ValueError("leaf names differ: %r"
                     % sorted(set(program) ^ set(reference))[:6])
  median = statistics.median(reference.values())
  return max(abs(program[k] - reference[k]) / max(reference[k], median)
             for k in reference)
