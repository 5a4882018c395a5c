"""The two ``slab_in_place_share`` readers (CPU only:
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``)."""

import pytest

from benchmarks.lib.loader import load_module


@pytest.mark.parametrize("cell", ["backlog", "steady"])
def test_reads_the_share_and_nothing_from_a_program_without_counters(cell):
  read = load_module("layer_metrics", "slab_in_place_share." + cell).read
  assert read(dict(stats_delta=dict(slab_dispatches=522,
                                    slab_in_place=522))) == 100.0
  assert read(dict(stats_delta=dict(slab_dispatches=4,
                                    slab_in_place=3))) == 75.0
  # the parent of PR 25 has no such counters; an idle window no dispatches
  assert read(dict(stats_delta=dict(steps=8))) is None
  assert read(dict(stats_delta=dict(slab_dispatches=0,
                                    slab_in_place=0))) is None
  assert read({}) is None
