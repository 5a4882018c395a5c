"""The two ``decode_attn_ragged_share`` readers (CPU only:
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``)."""

import json
import os

import pytest

from benchmarks.lib.loader import ROOT, load_module


@pytest.mark.parametrize("cell", ["backlog", "steady"])
def test_reads_the_share_and_nothing_from_a_program_without_counters(cell):
  read = load_module("layer_metrics", "decode_attn_ragged_share." + cell).read
  # 1179 dispatches of 4 steps over 36 layers, all by the kernel
  assert read(dict(stats_delta=dict(decode_attn_reads=169776,
                                    decode_attn_reads_ragged=169776))) \
      == 100.0
  # a stack of which half the layers have a sliding window
  assert read(dict(stats_delta=dict(decode_attn_reads=16,
                                    decode_attn_reads_ragged=8))) == 50.0
  # the CPU, a mesh, an int8 cache: the dense contraction everywhere
  assert read(dict(stats_delta=dict(decode_attn_reads=16,
                                    decode_attn_reads_ragged=0))) == 0.0
  # the parent of PR 31 has no such counters; an idle window (or the paged
  # pool and the Kimi stack, whose layers read otherwise) no such reads
  assert read(dict(stats_delta=dict(steps=8, cursor_leaf_writes=576,
                                    cursor_leaf_writes_dma=576))) is None
  assert read(dict(stats_delta=dict(decode_attn_reads=0,
                                    decode_attn_reads_ragged=0))) is None
  assert read({}) is None


def test_both_are_declared_for_the_cells_whose_step_reads_a_slab():
  with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    bench = json.load(f)
  by_name = {m["name"]: m for m in bench["per_layer"]}
  for name, cells, moves in (
      ("decode_attn_ragged_share.backlog",
       ["gpt2l-serve-backlog", "ouro-serve-backlog"], "serve_tok_s"),
      ("decode_attn_ragged_share.steady", ["gpt2l-serve-steady"],
       "tpot_p95_ms")):
    m = by_name[name]
    assert m["workloads"] == cells
    assert (m["unit"], m["better"], m["moves"], m["source"]) \
        == ("%", "higher", moves, "program_counter")
    assert m["layer"] == by_name["slab_in_place_share.backlog"]["layer"]
