"""The two ``cursor_write_dma_share`` readers (CPU only:
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``)."""

import json
import os

import pytest

from benchmarks.lib.loader import ROOT, load_module


@pytest.mark.parametrize("cell", ["backlog", "steady"])
def test_reads_the_share_and_nothing_from_a_program_without_counters(cell):
  read = load_module("layer_metrics", "cursor_write_dma_share." + cell).read
  # 874 dispatches of 4 steps over 72 leaves, all by the kernel
  assert read(dict(stats_delta=dict(cursor_leaf_writes=251712,
                                    cursor_leaf_writes_dma=251712))) == 100.0
  # an int8 cache: K and V by the kernel, their scale leaves by the loop
  assert read(dict(stats_delta=dict(cursor_leaf_writes=16,
                                    cursor_leaf_writes_dma=8))) == 50.0
  # the CPU, a mesh: the loop everywhere
  assert read(dict(stats_delta=dict(cursor_leaf_writes=16,
                                    cursor_leaf_writes_dma=0))) == 0.0
  # the parent of PR 29 has no such counters; an idle window (or the paged
  # pool, which writes otherwise) no cursor writes
  assert read(dict(stats_delta=dict(steps=8, slab_dispatches=2))) is None
  assert read(dict(stats_delta=dict(cursor_leaf_writes=0,
                                    cursor_leaf_writes_dma=0))) is None
  assert read({}) is None


def test_both_are_declared_for_the_serving_cells():
  with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    bench = json.load(f)
  by_name = {m["name"]: m for m in bench["per_layer"]}
  for name, cells, moves in (
      ("cursor_write_dma_share.backlog",
       ["gpt2l-serve-backlog", "kimi-linear-serve-backlog"], "serve_tok_s"),
      ("cursor_write_dma_share.steady", ["gpt2l-serve-steady"],
       "tpot_p95_ms")):
    m = by_name[name]
    assert m["workloads"] == cells
    assert (m["unit"], m["better"], m["moves"], m["source"]) \
        == ("%", "higher", moves, "program_counter")
    assert m["layer"] == by_name["slab_in_place_share.backlog"]["layer"]
