#!/usr/bin/env python3
"""Builder's reading, never part of a check's run: do a serving cell's limits
FAIL the reference computed WITHOUT a piece of the configuration's
mathematics, put in the program's place?

    chiprun -- python3 benchmarks/tools/math_controls.py --workload <cell> --seed N

``benchmarks/tools/sink_control.py`` AS IT IS (the cell run as
``benchmarks/run.py`` runs it, the reference pass reading one more gap a
control, the cell's checks printed for the sound run and for each control in
its place), with the controls of the ``deepseek_v3`` family as the default:
``no_group`` (the router WITHOUT its group limit: the top 8 over all 256
experts), ``no_mscale`` (the softmax scale WITHOUT YaRN's ``m^2``) and ``fp8``,
all three on ONE run's served requests.  A program that left the group limit
or the scale's factor out would still serve fluent tokens; this says whether
the cell would fail it.  A file of its own because the PR that brought it may
not edit ``sink_control.py``, whose ``--controls`` default is MiMo's.  Writes
``chiprun_out/sink_control-<cell>-<seed>.json`` (the wrapped tool's name).
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)


def main(argv=None) -> int:
  from benchmarks.tools import sink_control
  argv = list(sys.argv[1:] if argv is None else argv)
  if not any(a.startswith("--controls") for a in argv):
    argv += ["--controls", "no_group,no_mscale,fp8"]
  return sink_control.main(argv)


if __name__ == "__main__":
  sys.exit(main())
