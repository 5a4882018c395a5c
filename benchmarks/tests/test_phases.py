"""Fast CPU tests of what PR 24 added to the yardstick: the readers of the
serving loop's counters, and the reduction that splits the device's idle time
over the program's regions (run with
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``)."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)

from benchmarks.lib import loader, phases, trace  # noqa: E402


def _events(name):
  ev = loader.load_json(os.path.join(HERE, name))
  return ev, dict(devices={k: [tuple(e) for e in v]
                           for k, v in ev["devices"].items()},
                  host=[tuple(e) for e in ev["host"]])


# -- the trace reduction ------------------------------------------------------


def test_innermost_region_names_the_idle_time():
  ev, events = _events("phases_fixture.json")
  s = phases.reduce_events(events, default_gap_label="engine-loop")
  got = {k: v * 1e9 for k, v in s["idle_gap_seconds"].items()}
  assert got == pytest.approx(ev["expect"]["idle_gap_ns"])
  # inside serve.decode and its serve.decode.harvest: the child's name
  assert got["serve.decode.harvest"] == pytest.approx(80.0)
  # what no region covers falls to the default label, bench. is stripped
  assert got["engine-loop"] == pytest.approx(60.0) and "submit" in got
  assert phases.reduce_events(events)["idle_gap_seconds"][
      "unattributed"] == pytest.approx(60e-9)
  # the pieces are a partition of the idle time
  idle_s = s["idle_share"] * s["window_s"]
  assert sum(s["idle_gap_seconds"].values()) == pytest.approx(idle_s)
  assert s["idle_gaps"][0][0] == "serve.decode.harvest"
  shares = phases.idle_shares(s)
  want = ev["expect"]["idle_share_ns"]
  assert shares == pytest.approx(
      {k: 100.0 * v / ev["expect"]["window_ns"] for k, v in want.items()})
  assert sum(shares.values()) == pytest.approx(100.0 * s["idle_share"])


def test_everything_an_accepted_metric_reads_is_trace_pys():
  """On the trace the v5e recorded (PR 23's fixture, ``bench.`` events only)
  and on the nested one: busy time, span, idle share, kernels and operation
  groups are ``trace.reduce_events``'s own, only the gaps' labels differ."""
  for name in ("trace_fixture.json", "phases_fixture.json"):
    _, events = _events(name)
    old = trace.reduce_events(dict(events, host=[
        e for e in events["host"] if e[0].startswith("bench.")]))
    new = phases.reduce_events(events)
    for key in old:
      if key not in ("idle_gap_seconds", "idle_gaps"):
        assert new[key] == old[key], key
    assert sum(new["idle_gap_seconds"].values()) == pytest.approx(
        sum(old["idle_gap_seconds"].values()))
  # the train cell's labels stay the runner's: no region on the train path
  assert set(new["idle_gap_seconds"]) - {"unattributed"} <= {
      "serve.decode", "serve.decode.fetch", "serve.decode.harvest",
      "serve.admit", "serve.prefill", "serve.prefill.chunk",
      "serve.prefill.sync", "serve.insert", "submit"}
  _, train = _events("trace_fixture.json")
  labels = set(phases.reduce_events(train)["idle_gap_seconds"])
  assert {"feed_wait", "dispatch", "loss_fetch"} <= labels
  assert labels <= {"feed_wait", "dispatch", "loss_fetch", "unattributed"}


def test_leaf_segments_are_disjoint_and_survive_overlap():
  host = [("serve.a", 0.0, 10.0), ("serve.b", 5.0, 10.0),   # two threads
          ("serve.c", 30.0, 5.0)]
  seg = phases.leaf_segments(host)
  assert seg == [(0.0, 5.0, "serve.a"), (5.0, 15.0, "serve.b"),
                 (30.0, 35.0, "serve.c")]
  assert phases.split_gaps([(2.0, 32.0)], seg) == {
      "serve.a": [3e-9], "serve.b": [1e-8], "unattributed": [1.5e-8],
      "serve.c": [2e-9]}
  assert phases.reduce_events(dict(devices={}, host=host)) is None


# -- the counter readers ------------------------------------------------------


def _report(**delta):
  d = dict(steps=1000, prefills=200, prefill_chunks=980,
           decode_dispatches=250, t_reap_s=0.1, t_idle_s=0.0, t_admit_s=0.4,
           t_prefill_s=8.0, t_prefill_sync_s=2.0, t_insert_s=0.5,
           t_decode_prep_s=0.2, t_decode_dispatch_s=1.0, t_decode_fetch_s=29.0,
           t_decode_harvest_s=8.3)
  d.update(delta)
  return dict(stats_delta=d, window_s=50.0,
              device=dict(platform="tpu", kind="TPU v5 lite", count=1))


@pytest.mark.parametrize("metric,want", [
    ("decode_step_inner_ms.backlog", 30.0),
    ("decode_step_inner_ms.steady", 30.0),
    ("loop_host_share.backlog", 18.0),
    ("loop_host_share.steady", 18.0),
    ("prefill_chunks_per_prompt", 4.9),
])
def test_counter_readers(metric, want):
  read = loader.load_module("layer_metrics", metric).read
  assert read(_report()) == pytest.approx(want)
  # the parent's program has no such counter: nothing to read, no raise
  old = _report()
  for k in list(old["stats_delta"]):
    if k.startswith("t_") or k in ("prefill_chunks", "decode_dispatches"):
      del old["stats_delta"][k]
  assert read(old) is None
  assert read(dict(window_s=50.0)) is None and read({}) is None
  # a rehearsal's host seconds are not a device's: only the count reads
  cpu = dict(_report(), device=dict(platform="cpu", kind="cpu", count=1))
  assert (read(cpu) is None) == (metric != "prefill_chunks_per_prompt")


def test_counter_readers_survive_an_empty_window():
  for metric in ("decode_step_inner_ms.backlog", "prefill_chunks_per_prompt"):
    read = loader.load_module("layer_metrics", metric).read
    assert read(_report(steps=0, prefills=0)) is None


def test_phase_seconds_close_on_the_window():
  sec = phases.phase_seconds(_report())
  assert set(sec) == set(phases.PHASE_KEYS)
  assert sum(sec.values()) == pytest.approx(49.5)
  assert phases.phase_seconds(dict(stats_delta=dict(steps=3))) is None


def test_new_entries_keep_the_contract():
  """The five entries are appended, each lists its one cell, and that cell
  reports the end-to-end metric the entry moves."""
  b = loader.load_json(os.path.join(ROOT, "BENCHMARK.json"))
  names = [m["name"] for m in b["per_layer"]]
  new = names[-5:]
  assert new == ["decode_step_inner_ms.backlog", "decode_step_inner_ms.steady",
                 "loop_host_share.backlog", "loop_host_share.steady",
                 "prefill_chunks_per_prompt"]
  e2e = {m["name"]: m for m in b["end_to_end"]}
  for m in b["per_layer"][-5:]:
    assert m["source"] == "program_counter" and len(m["workloads"]) == 1
    assert m["workloads"][0] in e2e[m["moves"]]["workloads"]
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
