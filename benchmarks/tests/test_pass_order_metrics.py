"""The two readers of the pass order's counters (CPU only:
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``)."""

import json
import os

import pytest

from benchmarks.lib.loader import ROOT, load_module

BACKLOG_CELLS = ["gpt2l-serve-backlog", "kimi-linear-serve-backlog",
                 "ouro-serve-backlog", "trinity-serve-backlog",
                 "mimo-serve-backlog"]


@pytest.mark.parametrize("metric, over, of", [
    ("prefill_behind_decode_share.backlog", "prefill_chunks_behind_decode",
     "prefill_chunks"),
    ("admit_ahead_share.backlog", "admits_ahead", "prefills")])
def test_a_share_of_the_windows_count_and_nothing_without_the_counter(
    metric, over, of):
  read = load_module("layer_metrics", metric).read
  assert read(dict(stats_delta={of: 1533, over: 1022})) == 100.0 * 1022 / 1533
  assert read(dict(stats_delta={of: 40, over: 0})) == 0.0
  # the parent of PR 39 counts chunks and prompts only; an idle window
  # admitted nothing; a report without a delta reads nothing
  assert read(dict(stats_delta={of: 1533})) is None
  assert read(dict(stats_delta={of: 0, over: 0})) is None
  assert read(dict(stats_delta=None)) is None and read({}) is None


def test_both_are_declared_for_the_five_backlog_cells():
  with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    bench = json.load(f)
  by_name = {m["name"]: m for m in bench["per_layer"]}
  for name in ("prefill_behind_decode_share.backlog",
               "admit_ahead_share.backlog"):
    m = by_name[name]
    assert m["workloads"] == BACKLOG_CELLS
    assert (m["unit"], m["better"], m["moves"], m["source"]) \
        == ("%", "higher", "serve_tok_s", "program_counter")
    assert m["layer"] == by_name["slot_occupancy"]["layer"]
