"""The one general traffic generator: a traffic file's parameters plus
``--seed`` give the inputs.  Every seed gets the SAME set of sizes and
arrivals in another order (so a seed changes the order of the work and never
its amount), and its own token contents.

Serving idea copied from ``tools/serve_bench.py`` (``make_workload``: seeded
mixed lengths, small common, large rare); the sizes, the fixed pool and the
arrival schedule are the benchmark's own.
"""



def _rng(seed: int, stream: int):
  import numpy as np
  return np.random.default_rng([int(seed), int(stream)])


# -- training rows -----------------------------------------------------------


def train_table(seed: int, rows: int, seq: int, vocab: int):
  """``[rows, seq]`` int32 token rows, all different."""
  import numpy as np
  return _rng(seed, 1).integers(0, vocab, (rows, seq), dtype=np.int32)


def train_partitions(table, rows_per_partition: int, partitions: int):
  """Partition ``p`` holds the table's rows ``p*R .. (p+1)*R`` (cyclic), as
  the list of rows ``cluster.train`` takes."""
  n = len(table)
  out = []
  for p in range(partitions):
    idx = [(p * rows_per_partition + i) % n
           for i in range(rows_per_partition)]
    out.append([table[i] for i in idx])
  return out


def expected_rows(table, start: int, count: int):
  """Rows ``start .. start+count`` of the fed stream (what the node must
  see, in order)."""
  import numpy as np
  return table[(start + np.arange(count)) % len(table)]


# -- serving requests --------------------------------------------------------


def size_pool(mix: dict):
  """The fixed multiset of (prompt_len, output_len) a mix stands for:
  ``pool`` requests, each length drawn by its weight over the grid with the
  file's own ``mix_seed`` (never ``--seed``); pairs whose sum passes
  ``max_total`` shorten the output."""
  import numpy as np
  rng = _rng(mix["mix_seed"], 2)

  def counts(grid, weights, n):
    w = np.asarray(weights, float)
    c = np.floor(w / w.sum() * n).astype(int)
    order = np.argsort(-(w / w.sum() * n - c))
    for i in order[:n - c.sum()]:
      c[i] += 1
    return np.repeat(np.asarray(grid), c)

  n = int(mix["pool"])
  prompts = rng.permutation(counts(mix["prompt_lens"], mix["prompt_weights"], n))
  outs = rng.permutation(counts(mix["output_lens"], mix["output_weights"], n))
  outs = np.minimum(outs, int(mix["max_total"]) - prompts)
  if outs.min() < 2:
    raise ValueError("a pair leaves fewer than 2 output tokens")
  return [(int(p), int(o)) for p, o in zip(prompts, outs)]


def request_stream(mix: dict, seed: int, vocab: int):
  """Endless iterator of ``(prompt tokens, output_len)``: the pool in a
  seeded order, then again in another order, and so on.  Token contents
  always come from ``seed``; with ``mix["order"] == "fixed"`` the ORDER comes
  from the file's ``mix_seed`` (a tail latency is a property of one schedule:
  another order is other work)."""
  import numpy as np
  pool = size_pool(mix)
  fixed = mix.get("order", "seed") == "fixed"
  order_rng = _rng(mix["mix_seed"] if fixed else seed, 3)
  tok_rng = _rng(seed, 4)
  while True:
    for i in order_rng.permutation(len(pool)):
      plen, olen = pool[i]
      yield tok_rng.integers(0, vocab, plen, dtype=np.int32), olen


def poisson_arrivals(rate: float, seconds: float, seed: int):
  """Due times (seconds from the start) of a Poisson process at ``rate``
  over ``seconds``.  The NUMBER of arrivals is fixed at round(rate*seconds)
  for every seed: sorted uniforms, which is a Poisson process conditioned on
  its count."""
  import numpy as np
  n = max(1, int(round(rate * seconds)))
  return np.sort(_rng(seed, 5).uniform(0.0, seconds, n)).tolist()
