"""Tier-1 rehearses the benchmark the driver runs.

One case a cell of ``BENCHMARK.json``, read when the tests are collected (a
later cell adds its own case without an edit here): the cell's whole path
through ``benchmarks/run.py`` at the toy sizes its data files carry under
``"rehearse"``, on the CPU, with the comparison that decides ``correct``; and
the other half, that the same command without ``--rehearse`` fails where
there is no TPU and prints no result line. The test reads the benchmark and
edits nothing in it.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
  CELLS = [w["name"] for w in json.load(_f)["workloads"]]


def _run(cell, *extra, timeout):
  env = dict(os.environ, JAX_PLATFORMS="cpu")
  # what the chip machine would not have either
  for var in ("TOS_TPU_TEST_MODE", "ALLOW_MULTIPLE_LIBTPU_LOAD", "XLA_FLAGS"):
    env.pop(var, None)
  return subprocess.run(
      [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
       "--workload", cell, "--seed", "2147483665", "--seconds", "2",
       "--trace", "0"] + list(extra),
      cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout)


def _result_lines(stdout):
  """The harness's result is one JSON object on a line of its own."""
  out = []
  for line in stdout.splitlines():
    if line.startswith("{"):
      try:
        out.append(json.loads(line))
      except ValueError:
        pass
  return out


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_correct_on_the_cpu(cell):
  res = _run(cell, "--rehearse", timeout=480)
  assert res.returncode == 0, (res.stdout[-3000:], res.stderr[-3000:])
  done = [ln for ln in res.stdout.splitlines() if "rehearsal done:" in ln]
  assert len(done) == 1, res.stdout[-3000:]
  assert "correct=True" in done[0] and "failed=0" in done[0], done[0]
  assert _result_lines(res.stdout) == []


@pytest.mark.parametrize("cell", CELLS)
def test_cell_without_a_tpu_fails_and_prints_no_result(cell):
  res = _run(cell, timeout=240)
  assert res.returncode != 0, res.stdout[-3000:]
  assert "rehearsal done:" not in res.stdout
  assert _result_lines(res.stdout) == []
