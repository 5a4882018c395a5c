"""The grouped expert product's four readers and their count function, on a
recorded report (CPU only: ``JAX_PLATFORMS=cpu python -m pytest
benchmarks/tests -q``)."""

import json
import os

import pytest

from benchmarks.lib import needs_deepseek_v3, needs_expert_product
from benchmarks.lib import needs_mimo_v2_flash, needs_trinity
from benchmarks.lib.loader import ROOT, load_module

#: the reader's short name -> (its cell, the cell's sizes, the bytes of ONE
#: matrix of a held expert: hidden x moe_intermediate bf16 numbers)
CELLS = {
    "trinity": ("trinity-serve-backlog", needs_trinity, 3072 * 3072 * 2),
    "mimo": ("mimo-serve-backlog", needs_mimo_v2_flash, 4096 * 2048 * 2),
    "deepseek": ("deepseek-v3-serve-backlog", needs_deepseek_v3,
                 7168 * 2048 * 2),
}
SHARE = "expert_product_kernel_share.backlog"


def _read(name, report):
  return load_module("layer_metrics", name).read(report)


def _report(needs, **kernels):
  """A window of 1000 steps in which a layer of a step touched 9 held experts
  on average, beside a trace of 1200 calls of the kernel in 0.6 s."""
  layers = needs.sizes()["expert_layers"]
  keys = ("steps", "live_slot_steps", "moe_assignments_held",
          "moe_group_hits", "live_context_tokens", "window_context_tokens",
          "decode_attn_reads", "decode_attn_reads_ragged",
          "decode_attn_reads_ring")
  d = dict(dict.fromkeys(keys, 1000), moe_experts_touched=9 * 1000 * layers)
  return dict(stats_delta=d, device=dict(platform="tpu", kind="TPU v5 lite"),
              trace_summary=dict(kernels=kernels or {
                  "%expert_product": dict(seconds=0.6, calls=1200.0),
                  "%decode_attention": dict(seconds=0.1, calls=400.0)}))


@pytest.mark.parametrize("short", sorted(CELLS))
def test_the_roofline_counts_the_touched_matrices_once(short):
  _, needs, matrix = CELLS[short]
  assert needs.sizes()["expert_params"] == 3 * matrix // 2
  rep = _report(needs)
  # 1200 calls x 9 matrices at 819 GB/s, over the 0.6 s the kernel took
  least = 1200 * 9 * matrix / 819e9
  assert needs_expert_product.least_seconds(rep, needs) \
      == pytest.approx((least, 0.6))
  got = _read("expert_product_roofline." + short, rep)
  assert got == pytest.approx(100 * least / 0.6) and 0 < got < 100
  # a trace without the kernel (the parent of PR 41), a kernel that took no
  # time, a program without the counters, a window without a step: nothing
  assert _read("expert_product_roofline." + short, _report(
      needs, **{"%decode_attention": dict(seconds=0.1, calls=400.0)})) is None
  assert _read("expert_product_roofline." + short, _report(
      needs, **{"%expert_product": dict(seconds=0.0, calls=0.0)})) is None
  assert _read("expert_product_roofline." + short,
               dict(rep, stats_delta={})) is None
  assert _read("expert_product_roofline." + short, dict(
      rep, stats_delta=dict(rep["stats_delta"], steps=0))) is None
  assert _read("expert_product_roofline." + short,
               dict(rep, trace_summary=None)) is None


def test_the_share_is_the_kernels_products_of_all():
  # 113 dispatches of 4 steps and 40 chunks over 4 expert layers, all three
  # products of each by the kernel
  n = 3 * 4 * (113 * 4 + 40)
  assert _read(SHARE, dict(stats_delta=dict(
      expert_products=n, expert_products_kernel=n))) == 100.0
  assert _read(SHARE, dict(stats_delta=dict(
      expert_products=n, expert_products_kernel=n // 4))) == 25.0
  # the rehearsal's toy widths, a float32 stack, a mesh: ragged_dot everywhere
  assert _read(SHARE, dict(stats_delta=dict(
      expert_products=n, expert_products_kernel=0))) == 0.0
  # the parent of PR 41 has no such counters; a model without expert layers
  # no such products
  assert _read(SHARE, dict(stats_delta=dict(steps=8))) is None
  assert _read(SHARE, dict(stats_delta=dict(
      expert_products=0, expert_products_kernel=0))) is None
  assert _read(SHARE, {}) is None


def test_they_are_declared_for_the_cells_that_hold_experts():
  """Looked up BY NAME: a later PR appends after them."""
  with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    bench = json.load(f)
  by_name = {m["name"]: m for m in bench["per_layer"]}
  cells = {w["name"] for w in bench["workloads"]}
  share = by_name[SHARE]
  assert share["workloads"] == [
      "kimi-linear-serve-backlog", "trinity-serve-backlog",
      "mimo-serve-backlog", "deepseek-v3-serve-backlog"]
  assert (share["unit"], share["better"], share["moves"], share["source"]) \
      == ("%", "higher", "serve_tok_s", "program_counter")
  assert share["layer"] \
      == by_name["decode_attn_ragged_share.backlog"]["layer"]
  for short, (cell, _, _) in CELLS.items():
    m = by_name["expert_product_roofline." + short]
    assert m["workloads"] == [cell] and cell in cells
    assert (m["unit"], m["better"], m["moves"], m["source"]) \
        == ("%", "higher", "serve_tok_s", "device_trace")
    assert m["layer"] == by_name["decode_attention_roofline.mimo"]["layer"]
