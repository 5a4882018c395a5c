"""The serving loop's phases, read two ways.

**From the program's counters** (``ServingEngine.stats`` as the window's
delta, ``report["stats_delta"]``): the loop thread's SELF seconds by phase
(``t_reap_s`` .. ``t_decode_harvest_s``, written by ``obs.spans.region``)
and the dispatch counts ``decode_dispatches`` / ``prefill_chunks``.  The
per-layer readers ``decode_step_inner_ms.*``, ``loop_host_share.*`` and
``prefill_chunks_per_prompt`` are these functions; a program without the
counters (the parent of PR 24) reads ``None``.

**From the profiler's trace**: the same regions are ``TraceAnnotation``s on
the host plane of the ``.xplane.pb``, on the device's clock, so the device's
idle time can carry the name of the phase the loop thread was in.
:func:`reduce_events` is ``benchmarks.lib.trace.reduce_events`` with the idle
time split over the INNERMOST region open at each instant, the program's
planes included.  The harness does not call it: the runners reduce with
``trace.py``, which keeps ``bench.`` events only, and only a ``benchmark``
PR may edit either (PERF.md section 7 names the two lines it would change).
It is the builder's reading of a kept trace::

    python3 benchmarks/run.py --workload <cell> --trace 1 --keep-run-dir ...
    python3 -m benchmarks.lib.phases .bench_runs/<run>
"""

import json
import os
import sys

from benchmarks.lib import trace

#: the loop thread's counters, in loop order
PHASE_KEYS = ("t_reap_s", "t_idle_s", "t_admit_s", "t_prefill_s",
              "t_prefill_sync_s", "t_insert_s", "t_decode_prep_s",
              "t_decode_dispatch_s", "t_decode_fetch_s", "t_decode_harvest_s")
#: phases in which the loop thread has dispatched nothing and waits for nothing
HOST_ONLY_KEYS = ("t_reap_s", "t_admit_s", "t_decode_prep_s",
                  "t_decode_harvest_s")
#: host annotations kept besides the runners' own ``bench.``: the program's
PROGRAM_PLANES = ("serve.", "train.", "feed.")
UNATTRIBUTED = ("engine-loop", "unattributed")


# -- counters ----------------------------------------------------------------


def _delta(report, *keys):
  """The window's counter delta when it carries every one of ``keys``."""
  d = report.get("stats_delta")
  if not d or any(k not in d for k in keys):
    return None
  return d


def _on_chip(report) -> bool:
  """A host time that waits for the device is the device's only there."""
  return (report.get("device") or {}).get("platform") == "tpu"


def decode_step_inner_ms(report):
  """Dispatch plus wait for the token matrix, per decode step: what a step
  costs with the harvest, reap and admission bookkeeping left out."""
  d = _delta(report, "t_decode_dispatch_s", "t_decode_fetch_s", "steps")
  if d is None or not d["steps"] or not _on_chip(report):
    return None
  return 1e3 * (d["t_decode_dispatch_s"] + d["t_decode_fetch_s"]) / d["steps"]


def loop_host_share(report):
  """Percent of the window the loop thread spent in Python alone."""
  d = _delta(report, *HOST_ONLY_KEYS)
  if d is None or not report.get("window_s") or not _on_chip(report):
    return None
  return 100.0 * sum(d[k] for k in HOST_ONLY_KEYS) / report["window_s"]


def prefill_chunks_per_prompt(report):
  """Chunk dispatches per admitted prompt (a count: any platform)."""
  d = _delta(report, "prefill_chunks", "prefills")
  if d is None or not d["prefills"]:
    return None
  return d["prefill_chunks"] / d["prefills"]


def phase_seconds(report):
  """``{key: seconds}`` over the window, or ``None``; their sum closes on
  ``window_s`` (nothing of a loop pass is outside a region)."""
  d = _delta(report, *PHASE_KEYS)
  return None if d is None else {k: d[k] for k in PHASE_KEYS}


# -- trace -------------------------------------------------------------------


def load_host_events(path: str) -> list:
  """``[(name, start_ns, dur_ns)]``: the runners' ``bench.`` annotations and
  the program's regions from every host plane."""
  from jax.profiler import ProfileData
  keep = (trace.ANNOTATION_PREFIX,) + PROGRAM_PLANES
  return [(ev.name, float(ev.start_ns), float(ev.duration_ns))
          for plane in ProfileData.from_file(path).planes
          if plane.name.startswith("/host:")
          for line in plane.lines for ev in line.events
          if ev.name.startswith(keep)]


def leaf_segments(host) -> list:
  """``[(start, end, label)]``, sorted and disjoint: at every instant the
  INNERMOST open annotation (of those open, the one that started last), so
  time inside ``serve.decode`` and its ``serve.decode.harvest`` reads
  ``serve.decode.harvest``.  ``bench.`` is stripped, a program's name kept
  whole."""
  out, stack, cur = [], [], 0.0

  def close(upto):
    nonlocal cur
    while stack and stack[-1][0] <= upto:
      end, name = stack.pop()
      if end > cur:
        out.append((cur, end, name))
        cur = end

  for name, s, d in sorted(host, key=lambda e: (e[1], -e[2])):
    close(s)
    if stack and s > cur:
      out.append((cur, s, stack[-1][1]))
    cur = max(cur, s)
    if name.startswith(trace.ANNOTATION_PREFIX):
      name = name[len(trace.ANNOTATION_PREFIX):]
    stack.append((s + d, name))
  close(float("inf"))
  return out


def split_gaps(gaps, segments, default_label="unattributed") -> dict:
  """``{label: [seconds of each idle piece]}``: every gap cut at the
  segments' edges, each piece under its segment's label, what no segment
  covers under ``default_label``.  Both inputs sorted and disjoint."""
  pieces, i = {}, 0

  def add(lab, t0, t1):
    if t1 > t0:
      pieces.setdefault(lab, []).append((t1 - t0) / 1e9)

  for g0, g1 in gaps:
    while i < len(segments) and segments[i][1] <= g0:
      i += 1
    edge, j = g0, i
    while j < len(segments) and segments[j][0] < g1:
      s, e, lab = segments[j]
      add(default_label, edge, min(s, g1))
      add(lab, max(s, edge), min(e, g1))
      edge = max(edge, min(e, g1))
      j += 1
    add(default_label, edge, g1)
  return pieces


def reduce_events(events: dict, top: int = 10, default_gap_label=None):
  """``trace.reduce_events`` of the same events with ``bench.`` host events
  only (so every number an accepted metric reads is that file's), and its
  ``idle_gap_seconds`` / ``idle_gaps`` replaced by the TIME-SPLIT of the
  first device's gaps over :func:`leaf_segments` of ALL the host events
  given.  ``trace.py`` gives a whole gap to the one annotation that covers
  most of it; a serving gap of 30 ms spans a harvest, a reap, an admission
  and the next dispatch, and each should get its own milliseconds."""
  bench_only = dict(events, host=[
      e for e in events["host"] if e[0].startswith(trace.ANNOTATION_PREFIX)])
  summary = trace.reduce_events(bench_only, top=top,
                                default_gap_label=default_gap_label)
  if summary is None:
    return None
  devs = events["devices"]
  lo = min(e[1] for ops in devs.values() for e in ops)
  hi = max(e[1] + e[2] for ops in devs.values() for e in ops)
  first = next(iter(devs.values()))
  pieces = split_gaps(
      trace.gaps([(e[1], e[1] + e[2]) for e in first], lo, hi),
      leaf_segments(events["host"]), default_gap_label or "unattributed")
  summary["idle_gap_seconds"] = {k: sum(v) for k, v in pieces.items()}
  summary["idle_gaps"] = [[lab, s] for lab, s in sorted(
      ((lab, max(v)) for lab, v in pieces.items()),
      key=lambda kv: -kv[1])[:top]]
  return summary


def idle_shares(summary) -> dict:
  """Percent of the traced span the device idled, by what the loop thread
  was in: prefill (``serve.prefill*``, ``serve.insert``), decode
  (``serve.decode*``), other named regions, and no region at all.  The four
  sum to the idle share."""
  out = dict(prefill=0.0, decode=0.0, other=0.0, unattributed=0.0)
  for lab, sec in summary["idle_gap_seconds"].items():
    if lab.startswith("serve.prefill") or lab == "serve.insert":
      out["prefill"] += sec
    elif lab.startswith("serve.decode"):
      out["decode"] += sec
    elif lab in UNATTRIBUTED:
      out["unattributed"] += sec
    else:
      out["other"] += sec
  return {k: 100.0 * v / summary["window_s"] for k, v in out.items()}


def reduce_directory(directory: str, **kw):
  path = trace.find_xplane(directory)
  if path is None:
    return None
  events = trace.load_events(path)
  events["host"] = load_host_events(path)
  return reduce_events(events, **kw)


def main(argv) -> int:
  """Print one kept run's split: the trace's idle time by phase beside what
  ``trace.py`` reads of the same file, and the window's counters."""
  run_dir, out = argv[0], {}
  new = reduce_directory(run_dir, default_gap_label="engine-loop")
  if new is not None:
    old = trace.reduce_directory(run_dir, default_gap_label="engine-loop")
    out.update(
        idle_share_percent=100.0 * new["idle_share"],
        traced_s=new["window_s"], idle_shares=idle_shares(new),
        idle_gap_percent={k: 100.0 * v / new["window_s"] for k, v in sorted(
            new["idle_gap_seconds"].items(), key=lambda kv: -kv[1])},
        longest_piece_s=new["idle_gaps"],
        differs_from_trace_py=[k for k in sorted(old) if old[k] != new[k]],
        trace_py_idle_gap_seconds=old["idle_gap_seconds"],
        kernels=new["kernels"])
  report_path = os.path.join(run_dir, "serve.json")
  if os.path.exists(report_path):
    with open(report_path) as f:
      rep = json.load(f)
    sec = phase_seconds(rep)
    if sec is not None:
      d = rep["stats_delta"]
      out["window"] = dict(
          window_s=rep["window_s"], phase_seconds=sec,
          closure=sum(sec.values()) / rep["window_s"],
          steps=d["steps"], decode_dispatches=d["decode_dispatches"],
          prefills=d["prefills"], prefill_chunks=d["prefill_chunks"],
          decode_step_inner_ms=decode_step_inner_ms(rep),
          loop_host_share=loop_host_share(rep),
          prefill_chunks_per_prompt=prefill_chunks_per_prompt(rep))
  print(json.dumps(out))
  return 0 if out else 1


if __name__ == "__main__":
  sys.exit(main(sys.argv[1:]))
