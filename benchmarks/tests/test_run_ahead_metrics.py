"""The two readers of the run-ahead counter (CPU only:
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``)."""

import json
import os

import pytest

from benchmarks.lib.loader import ROOT, load_module

READERS = {"decode_run_ahead_share.backlog": ("serve_tok_s", [
               "gpt2l-serve-backlog", "kimi-linear-serve-backlog",
               "ouro-serve-backlog", "trinity-serve-backlog",
               "mimo-serve-backlog", "deepseek-v3-serve-backlog"]),
           "decode_run_ahead_share.steady": ("tpot_p95_ms",
                                             ["gpt2l-serve-steady"])}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_a_share_of_the_windows_dispatches_and_nothing_without_the_counter(
    metric):
  read = load_module("layer_metrics", metric).read
  assert read(dict(stats_delta=dict(decode_dispatches=1640,
                                    decode_dispatches_ahead=1638))) \
      == 100.0 * 1638 / 1640
  assert read(dict(stats_delta=dict(decode_dispatches=40,
                                    decode_dispatches_ahead=0))) == 0.0
  # the parent of PR 42 counts dispatches only; a window without a decode
  # dispatch has no share; a report without a delta reads nothing
  assert read(dict(stats_delta=dict(decode_dispatches=1640))) is None
  assert read(dict(stats_delta=dict(decode_dispatches=0,
                                    decode_dispatches_ahead=0))) is None
  assert read(dict(stats_delta=None)) is None and read({}) is None


def test_both_are_declared_for_the_cells_whose_metric_they_move():
  with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    bench = json.load(f)
  by_name = {m["name"]: m for m in bench["per_layer"]}
  reports = {m["name"]: m["workloads"] for m in bench["end_to_end"]
             if "workloads" in m}
  for name, (moves, cells) in READERS.items():
    m = by_name[name]
    assert m["workloads"] == cells
    assert set(cells) <= set(reports[moves])
    assert (m["unit"], m["better"], m["moves"], m["source"]) \
        == ("%", "higher", moves, "program_counter")
    assert m["layer"] == by_name["slot_occupancy"]["layer"]
