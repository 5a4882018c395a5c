"""The selection kernel's share on a recorded report (CPU only:
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``)."""

import json
import os

from benchmarks.lib.loader import ROOT, load_module

SHARE = "index_select_kernel_share.keye"


def _read(report):
  return load_module("layer_metrics", SHARE).read(report)


def test_the_share_is_the_kernels_selections_of_all():
  # 355 dispatches of 4 steps and 170 chunks over 6 layers, every search in
  # the kernel
  n = 6 * (355 * 4 + 170)
  assert _read(dict(stats_delta=dict(
      index_selections=n, index_selections_kernel=n))) == 100.0
  # the chunks' searches alone
  assert _read(dict(stats_delta=dict(
      index_selections=n, index_selections_kernel=6 * 170))) \
      == 100.0 * 170 / (355 * 4 + 170)
  # the rehearsal's toy widths, a mesh: the XLA search everywhere
  assert _read(dict(stats_delta=dict(
      index_selections=n, index_selections_kernel=0))) == 0.0


def test_a_report_without_the_counters_reads_nothing():
  # the parent of PR 45 has no such counters; a model without a selection
  # counts none
  assert _read(dict(stats_delta=dict(steps=8, decode_attn_reads_sparse=48))) \
      is None
  assert _read(dict(stats_delta=dict(
      index_selections=0, index_selections_kernel=0))) is None
  assert _read(dict(stats_delta=dict(index_selections=12))) is None
  assert _read(dict(stats_delta=None)) is None
  assert _read({}) is None


def test_it_is_declared_for_the_cell_that_selects():
  """Looked up BY NAME: a later PR appends after it."""
  with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    bench = json.load(f)
  by_name = {m["name"]: m for m in bench["per_layer"]}
  m = by_name[SHARE]
  assert m["workloads"] == ["keye-vl2-serve-backlog"]
  assert m["workloads"][0] in {w["name"] for w in bench["workloads"]}
  assert (m["unit"], m["better"], m["moves"], m["source"]) \
      == ("%", "higher", "serve_tok_s", "program_counter")
  assert m["layer"] == by_name["expert_product_kernel_share.backlog"]["layer"]
