"""Runner ``serve_engine_vs_control``: runner ``serve_engine`` AS IT IS (its
child, its load, its reference pass, its report and its four checks) with the
fp8 CONTROL read in EVERY run (the runner's own ``--control`` reading, which
is otherwise the builder's) over ``control_requests`` of the checked requests,
and ONE MORE number compared: the mean of those requests' served tokens'
reference-logit gaps OVER the mean gap of the tokens the fp8 reference puts
first at the same positions of the same requests, against
``limits["served_over_control_gap_mean_max"]``.

Why a ratio and not ``serve_engine_mean``'s absolute mean. A sparse model's
gap is made of moved near-ties (a router's k-th place; PERF.md section 4), and
how many near-ties a model HAS is a property of its seeded weights: in the
``keye-vl2-serve-backlog`` cell nineteen sound runs on nineteen seeds read
means of 0.0014 to 0.0127 (a factor of 9) and the fp8 control on twelve of
them 0.0066 to 0.0513: the two ranges overlap, float32 activations read no
better (0.0103 where bf16 read 0.0127 on the same seed), and no absolute limit
separates them (``limits_why`` in the traffic file). But a seed whose weights
have many near-ties has them for the fp8 reference too: in each run the
control reads 3.7 to 17 times the served tokens' mean, and the control put in
the program's place reads 1.0 by construction. The ratio takes the seed's own
tie density out of the number.

What the control costs, and why over SOME requests. A reference pass costs the
same whatever a request's length (``serve_engine`` pads it to ``max_seq``):
15.3 s a request in this cell and 16.9 s more for the fp8 pass, 135 s of a
warm run's 375 with the control over all 8 checked requests.  What a pass buys
is served TOKENS, so the control reads the ``control_requests`` checked
requests that were served the most tokens (the earlier in the sample first
among equals), and the ratio's numerator is the mean over those same requests,
not over all that were checked.  A run's requests are not alike (the longest
reads 0.1 to 0.7 alone, others 0 under both), so too few of them is another
number: the traffic file's ``control_requests_why`` has the readings by count.
``gap_by_request`` in the report keeps each checked request's tokens and sums.

A run whose control reads nothing (a reference pass that checked no token)
cannot vouch: the ratio is then missing and the check fails.  The absolute
mean stays as a limit for gross faults where the traffic file gives one.

The child dies with its parent (``PR_SET_PDEATHSIG``): a run cut from outside
leaves no process on the chip for the next run to meet.

A file of its own because the PR that brought it may not edit
``serve_engine.py`` or ``serve_engine_mean.py``: a ``benchmark`` PR can take
the check into ``serve_engine.checks_from`` (for a traffic file that gives the
limit) and delete this one.
"""

import json
import os
import signal

from benchmarks.lib import compare
from benchmarks.lib import loader

_base = loader.load_module("runners", "serve_engine")
_mean = loader.load_module("runners", "serve_engine_mean")

_PR_SET_PDEATHSIG = 1


def _die_with_parent(parent_pid: int) -> None:
  """Have the kernel kill this process when the one that started it dies."""
  import ctypes
  try:
    ctypes.CDLL(None, use_errno=True).prctl(
        _PR_SET_PDEATHSIG, int(signal.SIGKILL), 0, 0, 0)
  except (OSError, AttributeError):      # not Linux: as serve_engine's child
    return
  if os.getppid() != parent_pid:         # it died before the call above
    os._exit(1)


def _most_served(sample, k: int) -> list:
  """Indices of the ``k`` sampled requests with the most served tokens."""
  order = sorted(range(len(sample)), key=lambda i: -len(sample[i][1]))
  return sorted(order[:k])


def child_main(spec, report_path):
  """``serve_engine``'s child with its reference pass reading the control
  over ``control_requests`` requests; adds ``gap_by_request``,
  ``controlled_tokens`` and ``controlled_served_gap_mean`` to its report."""
  import numpy as np
  if spec.get("parent_pid") is not None:
    _die_with_parent(spec["parent_pid"])
  plain, rows = _base._reference_gaps, []

  def reference_gaps(family, config, seed, sample, max_seq, control):
    some = _most_served(sample, spec["traffic"]["control_requests"]) \
        if control else []
    rest = [i for i in range(len(sample)) if i not in some]
    gaps, ctl = [None] * len(sample), []
    if some:
      got, ctl = plain(family, config, seed, [sample[i] for i in some],
                       max_seq, True)
      for i, g in zip(some, got):
        gaps[i] = g
    if rest:
      got, _ = plain(family, config, seed, [sample[i] for i in rest],
                     max_seq, False)
      for i, g in zip(rest, got):
        gaps[i] = g
    for i, g in enumerate(gaps):
      c = ctl[some.index(i)] if i in some else None
      rows.append(dict(
          tokens=int(len(g)), served_sum=float(np.sum(g, dtype=np.float64)),
          control_sum=None if c is None
          else float(np.sum(c, dtype=np.float64))))
    return gaps, ctl

  _base._reference_gaps = reference_gaps
  try:
    _base.child_main(spec, report_path)
  finally:
    _base._reference_gaps = plain
  rep = loader.load_json(report_path)
  both = [r for r in rows if r["control_sum"] is not None]
  tokens = sum(r["tokens"] for r in both)
  rep.update(
      gap_by_request=rows, controlled_tokens=tokens,
      controlled_served_gap_mean=sum(r["served_sum"] for r in both) / tokens
      if tokens else None)
  with open(report_path + ".tmp", "w") as f:
    json.dump(rep, f)
  os.replace(report_path + ".tmp", report_path)


def checks_from(rep: dict, limits: dict) -> list:
  checks = (_mean if "served_logit_gap_mean_max" in limits
            else _base).checks_from(rep, limits)
  # a report of serve_engine's own child (the builder's tools) has the
  # control over every checked request
  served = rep.get("controlled_served_gap_mean", rep.get("served_gap_mean"))
  control = rep.get("control_gap_mean")
  ratio = served / control if served is not None and control else None
  return checks + [
      compare.check("served_over_control_gap_mean", ratio,
                    limits["served_over_control_gap_mean_max"])]


def run(spec: dict) -> dict:
  theirs = _base.child_main
  _base.child_main = child_main          # the child serve_engine.run starts
  try:
    rep = _base.run(dict(spec, control=True, parent_pid=os.getpid()))
  finally:
    _base.child_main = theirs
  rep["checks"] = checks_from(rep, spec["traffic"]["limits"])
  rep["notes"].append(
      "the control over the %d of %d checked requests served the most "
      "tokens: %d tokens, served mean %r over the control's %r"
      % (sum(1 for r in rep["gap_by_request"] if r["control_sum"] is not None),
         len(rep["gap_by_request"]), rep["controlled_tokens"],
         rep["controlled_served_gap_mean"], rep["control_gap_mean"]))
  return rep
