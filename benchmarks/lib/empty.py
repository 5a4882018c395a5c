"""The serving loop's known-drained seconds, read from the program's counters.

``ServingEngine.stats`` carries, beside each phase's ``t_*_s``, an
``empty_*_s``: the seconds of that phase in which the loop thread KNEW its
device drained, from the return of a blocking read of its newest program's
output to the return of its next dispatch call (``obs.spans.DeviceQueue``).
Their sum over the window is a LOWER bound of the device's idle time there
(launch latency, gaps inside a program and the copy back are idle the host
cannot vouch for; but for the last part of a dispatch call, where the
runtime starts the program before the call returns: PERF.md section 6, PR
36), split by what the host was doing, in every run, with no profiler.  The
per-layer readers ``device_empty_share.backlog``,
``empty_in_prefill_share.backlog``, ``empty_in_decode_share.backlog``,
``device_starved_share.steady``, ``prefill_dispatch_ms.backlog`` and
``decode_dispatch_ms.backlog`` are these functions; a program without the
counters (the parent of PR 36) reads ``None``, and so does a run off the
chip.

The trace's split of the same run is the check, phase by phase::

    python3 benchmarks/run.py --workload <cell> --trace 1 --keep-run-dir ...
    python3 -m benchmarks.lib.empty .bench_runs/<run>
"""

import json
import os
import sys

from benchmarks.lib import phases

#: the counter beside each of ``phases.PHASE_KEYS``, in loop order
EMPTY_KEYS = tuple("empty_" + k[2:] for k in phases.PHASE_KEYS)
#: from a request's pop to its row's insert
PREFILL_KEYS = ("empty_admit_s", "empty_prefill_s", "empty_prefill_sync_s",
                "empty_insert_s")
#: a decode pass, and the reap that follows its harvest
DECODE_KEYS = ("empty_reap_s", "empty_decode_prep_s",
               "empty_decode_dispatch_s", "empty_decode_fetch_s",
               "empty_decode_harvest_s")
#: the region(s) of a trace whose idle time a phase's counter should see
REGIONS = dict(
    t_reap_s=("serve.reap",), t_idle_s=("serve.idle",),
    t_admit_s=("serve.admit",),
    t_prefill_s=("serve.prefill", "serve.prefill.chunk"),
    t_prefill_sync_s=("serve.prefill.sync",), t_insert_s=("serve.insert",),
    t_decode_prep_s=("serve.decode.prep",),
    t_decode_dispatch_s=("serve.decode.dispatch",),
    t_decode_fetch_s=("serve.decode.fetch",),
    t_decode_harvest_s=("serve.decode.harvest",))


def _window_delta(report, *keys):
  """The delta of a window on the chip that carries ``keys``, or ``None``:
  a host second says something of the device only there."""
  if not report.get("window_s") or not phases._on_chip(report):
    return None
  return phases._delta(report, *keys)


def empty_share(report, keys=EMPTY_KEYS):
  """Percent of the window the device was known drained, in ``keys``."""
  d = _window_delta(report, *keys)
  if d is None:
    return None
  return 100.0 * sum(d[k] for k in keys) / report["window_s"]


def starved_share(report):
  """Percent of the window's time WITH a request (the window less the idle
  phase) in which the device was known drained: an empty engine is left out
  of both."""
  d = _window_delta(report, "t_idle_s", *EMPTY_KEYS)
  if d is None:
    return None
  served = report["window_s"] - d["t_idle_s"]
  if served <= 0:
    return None
  return 100.0 * (sum(d[k] for k in EMPTY_KEYS) - d["empty_idle_s"]) / served


def ms_per(report, seconds_key, count_key):
  """A phase's loop-thread milliseconds a dispatch it counts."""
  d = phases._delta(report, seconds_key, count_key)
  if d is None or not d[count_key] or not phases._on_chip(report):
    return None
  return 1e3 * d[seconds_key] / d[count_key]


def main(argv) -> int:
  """Print one kept run's table: by phase, the window's counters (seconds,
  and points of the window) beside the trace's idle time under the phase's
  regions (points of the traced span)."""
  run_dir = argv[0]
  with open(os.path.join(run_dir, "serve.json")) as f:
    rep = json.load(f)
  d = phases._delta(rep, *(phases.PHASE_KEYS + EMPTY_KEYS))
  if d is None:
    print(json.dumps(dict(error="the report's delta lacks the counters")))
    return 1
  split = phases.reduce_directory(run_dir, default_gap_label="engine-loop")
  gaps = {} if split is None else {
      k: 100.0 * v / split["window_s"]
      for k, v in split["idle_gap_seconds"].items()}
  w, rows, named = rep["window_s"], [], set()
  for t_key, e_key in zip(phases.PHASE_KEYS, EMPTY_KEYS):
    named.update(REGIONS[t_key])
    rows.append(dict(
        phase=t_key[2:-2], t_s=d[t_key], empty_s=d[e_key],
        t_points=100.0 * d[t_key] / w, empty_points=100.0 * d[e_key] / w,
        trace_idle_points=sum(gaps.get(r, 0.0) for r in REGIONS[t_key])))
  out = dict(
      window_s=w, rows=rows,
      empty_points=sum(r["empty_points"] for r in rows),
      trace_idle_points=None if split is None else 100.0 * split["idle_share"],
      trace_idle_elsewhere={k: v for k, v in gaps.items() if k not in named},
      traced_s=None if split is None else split["window_s"],
      device_empty_share=empty_share(rep),
      empty_in_prefill_share=empty_share(rep, PREFILL_KEYS),
      empty_in_decode_share=empty_share(rep, DECODE_KEYS),
      device_starved_share=starved_share(rep),
      prefill_dispatch_ms=ms_per(rep, "t_prefill_s", "prefill_chunks"),
      decode_dispatch_ms=ms_per(rep, "t_decode_dispatch_s",
                                "decode_dispatches"))
  print(json.dumps(out))
  return 0


if __name__ == "__main__":
  sys.exit(main(sys.argv[1:]))
