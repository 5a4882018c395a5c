"""The two readers of the padded prefill plan's counters (CPU only:
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``)."""

import json
import os

from benchmarks.lib.loader import ROOT, load_module


def test_chunks_per_prompt_in_the_backlog_cell():
  read = load_module("layer_metrics", "prefill_chunks_per_prompt.backlog").read
  assert read(dict(stats_delta=dict(prefills=200, prefill_chunks=216))) == 1.08
  assert read(dict(stats_delta=dict(prefills=200, prefill_chunks=978))) == 4.89
  # an idle window admitted nothing; a report without counters reads nothing
  assert read(dict(stats_delta=dict(prefills=0, prefill_chunks=0))) is None
  assert read(dict(stats_delta=dict(steps=8))) is None
  assert read({}) is None


def test_pad_share_and_nothing_from_a_program_without_the_counters():
  read = load_module("layer_metrics", "prefill_pad_share.backlog").read
  assert read(dict(stats_delta=dict(prefill_tokens=21616,
                                    prefill_padded_tokens=6498))) \
      == 100.0 * 6498 / 21616
  # a model that keeps the exact plan pads nothing
  assert read(dict(stats_delta=dict(prefill_tokens=512,
                                    prefill_padded_tokens=0))) == 0.0
  # the parent of PR 27 counts chunks and prompts only
  assert read(dict(stats_delta=dict(prefills=200, prefill_chunks=978))) is None
  assert read(dict(stats_delta=dict(prefill_tokens=0,
                                    prefill_padded_tokens=0))) is None
  assert read({}) is None


def test_both_are_declared_for_the_backlog_cell_alone():
  with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    bench = json.load(f)
  by_name = {m["name"]: m for m in bench["per_layer"]}
  for name, unit in (("prefill_chunks_per_prompt.backlog", "chunks/prompt"),
                     ("prefill_pad_share.backlog", "%")):
    m = by_name[name]
    assert m["workloads"] == ["gpt2l-serve-backlog"]
    assert (m["unit"], m["better"], m["moves"], m["source"]) \
        == (unit, "lower", "serve_tok_s", "program_counter")
    assert m["layer"] == by_name["slot_occupancy"]["layer"]
