"""The benchmark's readers of the serving loop's ``empty_*_s`` counters
(``benchmarks/lib/empty.py`` and six files of ``benchmarks/layer_metrics/``),
on a fixture report, found the way the harness finds them: by the name
``BENCHMARK.json`` gives, through ``benchmarks.lib.loader``. No JAX.
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
  sys.path.insert(0, REPO)

from benchmarks.lib import empty, loader, phases  # noqa: E402

BACKLOG = ["gpt2l-serve-backlog", "kimi-linear-serve-backlog",
           "ouro-serve-backlog", "trinity-serve-backlog"]
#: metric -> (unit, the end-to-end metric it moves, the cells ISSUE 36 lists)
ENTRIES = {
    "device_empty_share.backlog": ("%", "serve_tok_s", BACKLOG),
    "empty_in_prefill_share.backlog": ("%", "serve_tok_s", BACKLOG),
    "empty_in_decode_share.backlog": ("%", "serve_tok_s", BACKLOG),
    "device_starved_share.steady": ("%", "tpot_p95_ms",
                                    ["gpt2l-serve-steady"]),
    "prefill_dispatch_ms.backlog": ("ms", "serve_tok_s", BACKLOG),
    "decode_dispatch_ms.backlog": ("ms", "serve_tok_s", BACKLOG),
}
#: what each reads of `_report()`
WANT = {
    "device_empty_share.backlog": 100.0 * 15.5 / 50.0,
    "empty_in_prefill_share.backlog": 100.0 * 8.5 / 50.0,
    "empty_in_decode_share.backlog": 100.0 * 6.0 / 50.0,
    "device_starved_share.steady": 100.0 * 14.5 / 40.0,
    "prefill_dispatch_ms.backlog": 1e3 * 8.0 / 980,
    "decode_dispatch_ms.backlog": 1e3 * 1.0 / 250,
}
#: the older program's counters each reader needs (the rest came with PR 36)
OLD_KEYS = {"prefill_dispatch_ms.backlog": ("t_prefill_s", "prefill_chunks"),
            "decode_dispatch_ms.backlog": ("t_decode_dispatch_s",
                                           "decode_dispatches")}


def _report(**delta):
  """A window of 50 s of which the engine idled 10: 15.5 s known drained
  (1.0 of them idle, 8.5 round a prefill, 6.0 round a decode pass)."""
  d = dict(steps=1000, prefills=200, prefill_chunks=980,
           decode_dispatches=250, t_reap_s=0.1, t_idle_s=10.0, t_admit_s=0.4,
           t_prefill_s=8.0, t_prefill_sync_s=2.0, t_insert_s=0.5,
           t_decode_prep_s=0.2, t_decode_dispatch_s=1.0,
           t_decode_fetch_s=19.0, t_decode_harvest_s=8.3,
           empty_reap_s=0.1, empty_idle_s=1.0, empty_admit_s=0.3,
           empty_prefill_s=7.0, empty_prefill_sync_s=0.7, empty_insert_s=0.5,
           empty_decode_prep_s=0.0, empty_decode_dispatch_s=0.9,
           empty_decode_fetch_s=0.5, empty_decode_harvest_s=4.5)
  d.update(delta)
  return dict(stats_delta=d, window_s=50.0,
              device=dict(platform="tpu", kind="TPU v5 lite", count=1))


def _parent_report():
  """The same window of a program without the ``empty_*_s`` keys."""
  rep = _report()
  for k in empty.EMPTY_KEYS:
    del rep["stats_delta"][k]
  return rep


@pytest.mark.parametrize("metric", sorted(ENTRIES))
def test_reader_on_a_fixture_report(metric):
  read = loader.load_module("layer_metrics", metric).read
  assert read(_report()) == pytest.approx(WANT[metric])
  # off the chip a host second is not the device's: nothing to read
  cpu = dict(_report(), device=dict(platform="cpu", kind="cpu", count=1))
  assert read(cpu) is None
  assert read(dict(window_s=50.0)) is None and read({}) is None
  # the parent's delta has no empty_* key: only the two readers of older
  # counters find something, and nothing raises
  old = read(_parent_report())
  if metric in OLD_KEYS:
    assert old == pytest.approx(WANT[metric])
    bare = _parent_report()
    for k in OLD_KEYS[metric]:
      del bare["stats_delta"][k]
    assert read(bare) is None
  else:
    assert old is None
    one_short = _report()        # one of the keys it sums is missing
    del one_short["stats_delta"][
        "empty_reap_s" if "decode" in metric else "empty_insert_s"]
    assert read(one_short) is None


def test_the_two_shares_sum_to_the_first():
  whole, pre, dec = (
      loader.load_module("layer_metrics", m).read(_report(empty_idle_s=0.0))
      for m in ("device_empty_share.backlog",
                "empty_in_prefill_share.backlog",
                "empty_in_decode_share.backlog"))
  assert pre + dec == pytest.approx(whole)
  # the keys of the two are a partition of the ten less the idle phase's
  assert sorted(empty.PREFILL_KEYS + empty.DECODE_KEYS + ("empty_idle_s",)) \
      == sorted(empty.EMPTY_KEYS)
  assert empty.EMPTY_KEYS == tuple(
      "empty_" + k[2:] for k in phases.PHASE_KEYS)
  assert set(empty.REGIONS) == set(phases.PHASE_KEYS)


def test_readers_survive_an_empty_window():
  rep = _report(prefill_chunks=0, decode_dispatches=0, t_idle_s=50.0)
  for metric in ("prefill_dispatch_ms.backlog", "decode_dispatch_ms.backlog",
                 "device_starved_share.steady"):
    assert loader.load_module("layer_metrics", metric).read(rep) is None
  assert empty.empty_share(dict(_report(), window_s=0.0)) is None


def test_engine_writes_the_keys_the_readers_take():
  """The engine's ``stats`` names are the ones ``empty.py`` spells out."""
  from tensorflowonspark_tpu.obs import spans
  assert empty.EMPTY_KEYS == tuple(map(spans.empty_key, phases.PHASE_KEYS))


@pytest.mark.parametrize("metric", sorted(ENTRIES))
def test_entry_is_found_by_name_with_the_cells_the_issue_lists(metric):
  b = loader.load_json(os.path.join(REPO, "BENCHMARK.json"))
  (entry,) = [m for m in b["per_layer"] if m["name"] == metric]
  unit, moves, cells = ENTRIES[metric]
  assert set(entry) == {"name", "unit", "better", "source", "layer", "moves",
                        "workloads"}
  assert (entry["unit"], entry["moves"], entry["better"], entry["source"],
          entry["layer"]) == (unit, moves, "lower", "program_counter",
                              "serving scheduler and slots")
  # a later cell may be appended to the list: those the issue lists are there
  assert set(cells) <= set(entry["workloads"])
  # every cell listed reports the end-to-end metric the entry moves
  (e2e,) = [m for m in b["end_to_end"] if m["name"] == moves]
  known = {w["name"] for w in b["workloads"]}
  assert set(entry["workloads"]) <= set(e2e.get("workloads", known)) <= known
  # and the harness finds its reader
  assert callable(loader.load_module("layer_metrics", metric).read)


def test_kept_run_table_prints_counters_beside_nothing_without_a_trace(
    tmp_path, capsys):
  """``python3 -m benchmarks.lib.empty <kept run>`` on a run directory that
  holds a report and no trace: the window's rows, the trace's column 0."""
  with open(os.path.join(str(tmp_path), "serve.json"), "w") as f:
    json.dump(_report(), f)
  assert empty.main([str(tmp_path)]) == 0
  out = json.loads(capsys.readouterr().out)
  assert [r["phase"] for r in out["rows"]] == [
      k[2:-2] for k in phases.PHASE_KEYS]
  assert out["empty_points"] == pytest.approx(31.0)
  assert out["device_empty_share"] == pytest.approx(31.0)
  assert out["trace_idle_points"] is None
  assert all(r["trace_idle_points"] == 0.0 for r in out["rows"])
  with open(os.path.join(str(tmp_path), "serve.json"), "w") as f:
    json.dump(_parent_report(), f)
  assert empty.main([str(tmp_path)]) == 1
