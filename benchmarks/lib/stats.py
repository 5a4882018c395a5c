"""Metric arithmetic: percentiles, per-request serving latencies, spreads."""

import math
import statistics


def percentile(values, q: float):
  """The ``q``-th percentile (0..100), linear between ranks; ``inf`` entries
  (requests that failed) sort last, so enough failures reach the tail."""
  if not values:
    return None
  xs = sorted(values)
  if len(xs) == 1:
    return xs[0]
  pos = (len(xs) - 1) * q / 100.0
  lo, hi = int(math.floor(pos)), int(math.ceil(pos))
  if xs[hi] == math.inf:
    return math.inf
  return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def ttft_ms(req: dict) -> float:
  """Time to first token from when the request was DUE; a request that
  failed, was rejected or produced nothing misses every limit (``inf``)."""
  if req.get("error") or req.get("first_token_at") is None:
    return math.inf
  return (req["first_token_at"] - req["due_at"]) * 1e3


def tpot_ms(req: dict) -> float:
  """Time per output token after the first, per request."""
  if req.get("error") or req.get("finished_at") is None \
      or req.get("first_token_at") is None:
    return math.inf
  n = req["out_tokens"]
  if n < 2:
    return None
  return (req["finished_at"] - req["first_token_at"]) * 1e3 / (n - 1)


def latency_ms(req: dict) -> float:
  """Whole-request latency: due to finished."""
  if req.get("error") or req.get("finished_at") is None:
    return math.inf
  return (req["finished_at"] - req["due_at"]) * 1e3


def iqr_share(values) -> float:
  """Distance between first and third quartile as a share of the median
  (``statistics.quantiles(values, n=4)``): the spread a bound is set from."""
  q = statistics.quantiles(values, n=4)
  return (q[2] - q[0]) / statistics.median(values)
