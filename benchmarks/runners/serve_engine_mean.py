"""Runner ``serve_engine_mean``: runner ``serve_engine`` AS IT IS (its child,
its load, its reference pass, its report and its four checks) with ONE MORE
number compared: the MEAN of the served tokens' reference-logit gaps, against
``limits["served_logit_gap_mean_max"]``.

``serve_engine`` decides on the WIDEST gap alone, and for a sparse model of
few layers the widest gap cannot tell a sound run from the fp8 control: where
the k-th and (k+1)-th router scores of a token lie within rounding of each
other, a bf16 program and the float32 reference may take different experts,
and ONE such token reads a gap of up to 1.2 while a request in which no tie
moved reads a maximum under 0.08 (``benchmarks/tools/gap_where.py`` moves
that one tie in the reference and reads the gap again: PERF.md section 4).
The fp8 control meets the same near-ties, only more often, so its widest gap
is the same size; how OFTEN a gap is wide is what differs, and the mean
reads that (it is 10 to 27 times the sound runs' in the control).

A file of its own because the PR that brought it may not edit
``serve_engine.py``: a ``benchmark`` PR can take the check into
``serve_engine.checks_from`` (for a traffic file that gives the limit) and
delete this one.
"""

from benchmarks.lib import compare
from benchmarks.lib import loader

_base = loader.load_module("runners", "serve_engine")
child_main = _base.child_main


def checks_from(rep: dict, limits: dict) -> list:
  return _base.checks_from(rep, limits) + [
      compare.check("served_logit_gap_mean", rep["served_gap_mean"],
                    limits["served_logit_gap_mean_max"])]


def run(spec: dict) -> dict:
  rep = _base.run(spec)
  limits = spec["traffic"]["limits"]
  rep["checks"] = checks_from(rep, limits)
  if rep["control_gap_mean"] is not None:
    rep["notes"].append(
        "CONTROL in the program's place: served_logit_gap_mean %r against "
        "the limit %r: that check would pass: %s"
        % (rep["control_gap_mean"], limits["served_logit_gap_mean_max"],
           rep["control_gap_mean"] <= limits["served_logit_gap_mean_max"]))
  return rep
