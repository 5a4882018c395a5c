"""Observability-plane tests: metrics/spans/collector/export + the
rendezvous ``OBS`` verb (delta shipping, bounded-buffer drop accounting,
clock-offset estimation under injected chaos delay)."""

import json
import os
import sys
import threading
import time
from unittest import mock

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tensorflowonspark_tpu.control import rendezvous
from tensorflowonspark_tpu.obs import collector, export, metrics, spans
from tensorflowonspark_tpu.utils import chaos


@pytest.fixture()
def clean_active():
  """Tests that install a process registry/tracer must not leak it."""
  yield
  metrics.deactivate()
  spans.deactivate()


class TestMetrics:
  def test_counter_gauge_histogram_snapshot(self):
    r = metrics.MetricsRegistry()
    c = r.counter("c")
    c.inc()
    c.inc(3)
    r.gauge("g").set(2.5)
    h = r.histogram("h", bounds=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5)
    h.observe(100)
    snap = r.snapshot()
    assert snap["c"] == {"type": "counter", "value": 4}
    assert snap["g"] == {"type": "gauge", "value": 2.5}
    assert snap["h"]["counts"] == [1, 1, 1]    # <=1, <=10, overflow
    assert snap["h"]["count"] == 3 and snap["h"]["sum"] == 105.5
    # snapshots are plain builtins (msgpack/json-safe)
    json.dumps(snap)

  def test_same_name_different_type_rejected(self):
    r = metrics.MetricsRegistry()
    r.counter("x")
    with pytest.raises(TypeError):
      r.gauge("x")

  def test_delta_apply_roundtrip(self):
    """Deltas re-applied driver-side must reconstruct the totals — the
    OBS verb's whole shipping contract."""
    r = metrics.MetricsRegistry()
    c = r.counter("c")
    h = r.histogram("h", bounds=(1.0,))
    g = r.gauge("g")
    total = {}
    prev = r.snapshot()
    for i in range(3):
      c.inc(i + 1)
      h.observe(i)
      g.set(i)
      cur = r.snapshot()
      metrics.apply_delta(total, metrics.snapshot_delta(cur, prev))
      prev = cur
    final = r.snapshot()
    assert total["c"]["value"] == final["c"]["value"] == 6
    assert total["h"]["counts"] == final["h"]["counts"]
    assert total["h"]["count"] == 3
    assert total["g"]["value"] == 2      # gauge: last write, not a sum

  def test_delta_omits_unchanged(self):
    r = metrics.MetricsRegistry()
    r.counter("quiet")
    s1 = r.snapshot()
    assert metrics.snapshot_delta(r.snapshot(), s1) == {}
    r.counter("quiet").inc()
    d = metrics.snapshot_delta(r.snapshot(), s1)
    assert list(d) == ["quiet"] and d["quiet"]["value"] == 1

  def test_stats_snapshot_subtract_live_dict(self):
    """The one snapshot-subtract helper the benches route through: the
    live dict keeps mutating (daemon threads) and delta() reflects only
    the growth since the snapshot."""
    live = {"fetch_s": 1.0, "chunks": 3}
    snap = metrics.snapshot_stats(live)
    stop = threading.Event()

    def mutate():
      while not stop.is_set():
        live["fetch_s"] += 0.5
        live["chunks"] += 1

    t = threading.Thread(target=mutate, daemon=True)
    t.start()
    try:
      deadline = time.monotonic() + 5
      while live["chunks"] < 100 and time.monotonic() < deadline:
        time.sleep(0.01)
      d = snap.delta()
      assert d["chunks"] >= 97 and d["fetch_s"] >= 48.0
      assert snap.delta()["chunks"] >= d["chunks"]   # monotonic
    finally:
      stop.set()
      t.join(timeout=5)

  def test_active_registry_gated_by_env(self, clean_active):
    with mock.patch.dict("os.environ", {metrics.ENV_OBS: ""}):
      metrics.deactivate()
      assert metrics.active() is None
      assert spans.active() is None
    with mock.patch.dict("os.environ", {metrics.ENV_OBS: "1"}):
      reg = metrics.active()
      assert isinstance(reg, metrics.MetricsRegistry)
      assert metrics.active() is reg           # one per process
      assert isinstance(spans.active(), spans.SpanRecorder)
    # TOS_OBS=0 is off, not on
    with mock.patch.dict("os.environ", {metrics.ENV_OBS: "0"}):
      metrics.deactivate()
      assert metrics.active() is None


class TestSpans:
  def test_span_and_event_records(self):
    rec = spans.SpanRecorder(capacity=10)
    with rec.span("feed.batch", rows=32):
      time.sleep(0.01)
    rec.event("marker", kind="eof")
    got = rec.drain(None)
    assert len(got) == 2
    s, e = got
    assert s["name"] == "feed.batch" and s["ph"] == "X"
    assert s["dur"] >= 0.01 and s["attrs"] == {"rows": 32}
    assert e["ph"] == "i" and e["attrs"] == {"kind": "eof"}
    json.dumps(got)                       # wire-safe

  def test_bounded_buffer_drop_accounting(self):
    rec = spans.SpanRecorder(capacity=3)
    for i in range(7):
      rec.event("e%d" % i)
    assert len(rec) == 3
    assert rec.dropped == 4 and rec.recorded == 3
    assert rec.drop_counts() == {"spans_dropped": 4, "spans_recorded": 3}
    # drain frees capacity again
    assert len(rec.drain(None)) == 3
    rec.event("later")
    assert len(rec) == 1

  def test_clock_offset_keeps_min_rtt_sample(self):
    clk = spans.ClockOffset()
    assert clk.offset == 0.0 and clk.samples == 0
    clk.update(0.0, 5.0, 1.0)            # rtt 1.0, offset 4.5
    assert clk.offset == pytest.approx(4.5) and clk.rtt == 1.0
    clk.update(10.0, 14.7, 10.2)         # rtt 0.2: better, adopted
    assert clk.offset == pytest.approx(4.6) and clk.rtt == pytest.approx(0.2)
    clk.update(20.0, 99.0, 23.0)         # rtt 3.0: worse, ignored
    assert clk.offset == pytest.approx(4.6)
    assert clk.samples == 3

  def test_clock_offset_window_reelects_best_recent(self):
    """Once the elected sample ages out of the window, the MIN-RTT
    sample of the recent window is re-elected — never whatever lone
    (possibly delayed) sample happened to arrive at the boundary."""
    clk = spans.ClockOffset(window=2)
    clk.update(0.0, 5.0, 0.1)            # rtt 0.1: elected
    clk.update(1.0, 9.0, 1.4)            # rtt 0.4
    clk.update(3.0, 11.1, 3.3)           # rtt 0.3; window expired here
    # re-election picks the best of the last 2 samples (rtt 0.3), not
    # the stale rtt-0.1 winner and not blindly the newest
    assert clk.rtt == pytest.approx(0.3)
    assert clk.offset == pytest.approx(11.1 - 3.15)
    # a later delayed sample at a re-election boundary still loses to a
    # better sample inside the window
    clk.update(10.0, 14.2, 10.2)         # rtt 0.2: elected immediately
    clk.update(20.0, 99.0, 23.0)         # rtt 3.0
    clk.update(30.0, 99.5, 33.0)         # rtt 3.0; window expired
    assert clk.rtt == pytest.approx(3.0)
    assert clk.offset in (pytest.approx(99.0 - 21.5),
                          pytest.approx(99.5 - 31.5))


class _SinkServer:
  """A real rendezvous server with an attached ObsSink."""

  def __init__(self, sink=None):
    self.server = rendezvous.Server(1)
    self.server.obs_sink = sink
    self.addr = self.server.start()

  def close(self):
    self.server.stop()


class TestObsVerbAndCollector:
  def test_delta_shipping_end_to_end(self):
    """Shipper → OBS verb → sink: metric deltas accumulate server-side,
    spans arrive with the shipper's clock offset attached."""
    sink = collector.ObsSink()
    srv = _SinkServer(sink)
    reg = metrics.MetricsRegistry()
    rec = spans.SpanRecorder(capacity=100)
    shipper = collector.ObsShipper(srv.addr, 7, registry=reg, recorder=rec,
                                   interval=60, label="exec")
    try:
      reg.counter("work").inc(5)
      rec.event("phase1")
      assert shipper.ship(timeout=10)
      reg.counter("work").inc(2)
      assert shipper.ship(timeout=10)
      assert sink.metrics(7)["work"]["value"] == 7     # 5 + 2, not 5 + 7
      got = sink.obs_recv(16, timeout=5)
      assert [s["name"] for s in got] == ["phase1"]
      assert got[0]["executor_id"] == 7
      assert "offset" in got[0]
      # the OBS reply is a TIME exchange too
      assert shipper.clock.samples >= 1
      summary = sink.summary()
      assert summary["executors"][7]["ships"] == 2
      assert summary["rejected"] == 0
    finally:
      shipper.stop(timeout=2)
      srv.close()

  def test_idle_shipper_keeps_wire_quiet(self):
    sink = collector.ObsSink()
    srv = _SinkServer(sink)
    reg = metrics.MetricsRegistry()
    shipper = collector.ObsShipper(srv.addr, 1, registry=reg,
                                   recorder=spans.SpanRecorder(capacity=4),
                                   interval=60)
    try:
      reg.counter("x").inc()
      assert shipper.ship(timeout=10)
      before = sink.summary()["ingested"]
      assert shipper.ship(timeout=10)    # nothing new: acked locally
      assert sink.summary()["ingested"] == before
    finally:
      shipper.stop(timeout=2)
      srv.close()

  def test_rejected_ship_is_not_an_ack(self):
    """accepted=False (no sink / sink error) must NOT advance the
    metrics baseline: the delta re-ships once a sink is there."""
    srv = _SinkServer(sink=None)
    reg = metrics.MetricsRegistry()
    shipper = collector.ObsShipper(srv.addr, 3, registry=reg,
                                   recorder=spans.SpanRecorder(capacity=4),
                                   interval=60)
    try:
      reg.counter("work").inc(5)
      assert shipper.ship(timeout=10) is False
      assert shipper.ship_failures == 1 and shipper.ships_acked == 0
      sink = collector.ObsSink()
      srv.server.obs_sink = sink
      assert shipper.ship(timeout=10) is True
      assert sink.metrics(3)["work"]["value"] == 5   # nothing was lost
    finally:
      shipper.stop(timeout=2)
      srv.close()

  def test_obs_verb_without_sink_is_acked_and_dropped(self):
    srv = _SinkServer(sink=None)
    try:
      c = rendezvous.Client(srv.addr, timeout=5)
      resp = c._request({"type": "OBS", "executor_id": 0, "metrics": {},
                         "spans": []})
      assert resp["type"] == "OK" and resp["accepted"] is False
      assert "server_time" in resp
      c.close()
    finally:
      srv.close()

  def test_sink_bounded_span_buffer_drop_accounting(self):
    sink = collector.ObsSink(max_spans=3)
    msg = {"type": "OBS", "executor_id": 0, "metrics": {},
           "spans": [{"name": "s%d" % i, "ph": "i", "t0": float(i)}
                     for i in range(5)]}
    assert sink.ingest(msg)
    assert sink.spans_dropped == 2
    assert len(sink.obs_recv(10, timeout=1)) == 3
    assert sink.obs_recv(10, block=False) == []
    # malformed payloads are counted, never raised
    assert not sink.ingest({"type": "OBS"})
    assert sink.rejected == 1

  def test_ship_failure_counts_instead_of_raising(self):
    import socket
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()                            # nothing listens here
    rec = spans.SpanRecorder(capacity=10)
    rec.event("doomed")
    shipper = collector.ObsShipper(("127.0.0.1", port), 0,
                                   registry=metrics.MetricsRegistry(),
                                   recorder=rec, interval=60)
    assert shipper.ship(timeout=0.7) is False
    assert shipper.ship_failures >= 1
    assert shipper.spans_lost == 1       # drained spans counted, not kept
    shipper.stop(timeout=1)

  def test_clock_offset_estimation_under_chaos_rv_delay(self):
    """TOS_CHAOS_RV_DELAY on BEAT inflates individual round-trips; the
    min-RTT estimator must ride the clean beats: same-host monotonic
    clocks are shared, so the estimate must stay near zero even though
    the first beats saw a 0.2s injected delay (offset error up to 0.1s
    if they were trusted)."""
    chaos.reset()
    srv = _SinkServer()
    try:
      with mock.patch.dict("os.environ",
                           {chaos.ENV_RV_DELAY: "BEAT:0.2:2"}):
        sender = rendezvous.HeartbeatSender(srv.addr, 0, interval=0.05)
        sender.start()
        try:
          deadline = time.monotonic() + 10
          while sender.clock.samples < 5 and time.monotonic() < deadline:
            time.sleep(0.05)
        finally:
          sender.stop()
      assert sender.clock.samples >= 5
      # the adopted sample is a clean (undelayed) round-trip…
      assert sender.clock.rtt < 0.15
      # …so the offset error is bounded by rtt/2, far under the 0.1s a
      # delayed-sample estimate would carry
      assert abs(sender.clock.offset) <= sender.clock.rtt / 2 + 0.02
    finally:
      chaos.reset()
      srv.close()

  def test_beat_reply_carries_server_time(self):
    srv = _SinkServer()
    try:
      c = rendezvous.Client(srv.addr, timeout=5)
      resp = c._request({"type": "BEAT", "executor_id": 0})
      assert resp["type"] == "OK" and "server_time" in resp
      c.close()
    finally:
      srv.close()


class TestExport:
  def _clock(self, offset):
    clk = spans.ClockOffset()
    clk.update(0.0, offset, 0.0)         # rtt 0: exact offset
    return clk

  def test_process_log_merge_and_chrome_trace(self, tmp_path):
    d = str(tmp_path)
    log = export.ProcessLog(d, label="exec", executor_id=3,
                            clock=self._clock(2.0))
    log.append_spans([{"name": "feed.batch", "ph": "X", "t0": 1.0,
                       "dur": 0.5, "tid": "MainThread",
                       "attrs": {"rows": 8}}])
    log.close(metrics_snapshot={"c": {"type": "counter", "value": 4}})
    paths = export.find_logs(d)
    assert len(paths) == 1 and "obs-exec3-" in paths[0]
    procs = export.merge_jsonl(paths)
    assert len(procs) == 1
    p = procs[0]
    assert p["meta"]["label"] == "exec" and p["meta"]["executor_id"] == 3
    assert p["clock"]["offset"] == pytest.approx(2.0)
    assert p["metrics"]["c"]["value"] == 4
    assert export.anchored_window(p) == (pytest.approx(3.0),
                                         pytest.approx(3.5))
    trace = export.chrome_trace(procs)
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"process_name", "thread_name", "feed.batch"} <= names
    (span_ev,) = [e for e in trace["traceEvents"]
                  if e["name"] == "feed.batch"]
    assert span_ev["ph"] == "X"
    assert span_ev["ts"] == pytest.approx(3.0e6)      # anchored, µs
    assert span_ev["dur"] == pytest.approx(0.5e6)
    assert span_ev["args"] == {"rows": 8}
    json.dumps(trace)

  def test_merge_skips_malformed_lines(self, tmp_path):
    path = tmp_path / "obs-exec0-1.jsonl"
    path.write_text('{"kind": "meta", "label": "exec", "executor_id": 0, '
                    '"pid": 1, "t_wall": 0, "t_mono": 0}\n'
                    'not json\n'
                    '{"kind": "span", "name": "s", "ph": "i", "t0": 1.0}\n')
    (p,) = export.merge_jsonl([str(path)])
    assert p["skipped"] == 1 and len(p["spans"]) == 1

  def test_no_dir_is_a_noop(self, monkeypatch):
    monkeypatch.delenv(export.ENV_OBS_DIR, raising=False)
    log = export.ProcessLog(label="exec", executor_id=0)
    log.append_spans([{"name": "s", "ph": "i", "t0": 0.0}])
    log.close()
    assert log.path is None

  def test_prometheus_histogram_exposition(self):
    snap = {"feed.batch_ms": {"type": "histogram", "bounds": [1.0, 5.0],
                              "counts": [2, 1, 1], "sum": 10.0, "count": 4}}
    text = export.prometheus_text(snap, labels={"proc": "exec0"})
    lines = text.splitlines()
    assert lines[0] == "# TYPE tos_feed_batch_ms histogram"
    assert 'tos_feed_batch_ms_bucket{proc="exec0",le="1"} 2' in lines
    assert 'tos_feed_batch_ms_bucket{proc="exec0",le="5"} 3' in lines
    assert 'tos_feed_batch_ms_bucket{proc="exec0",le="+Inf"} 4' in lines
    assert 'tos_feed_batch_ms_count{proc="exec0"} 4' in lines


class TestStepTimerRegistrySeam:
  def test_step_timer_feeds_active_registry(self, clean_active):
    from tensorflowonspark_tpu.obs import profiler
    reg = metrics.activate()
    rec = spans.activate()
    t = profiler.StepTimer(warmup=1)
    for _ in range(3):
      with t.step(items=10):
        time.sleep(0.001)
    snap = reg.snapshot()
    assert snap["train.steps"]["value"] == 2        # warmup excluded
    assert snap["train.items"]["value"] == 20
    assert snap["train.step_ms"]["count"] == 2
    got = [s for s in rec.drain(None) if s["name"] == "train.step"]
    assert len(got) == 2 and got[0]["attrs"]["items"] == 10

  def test_step_timer_inert_without_registry(self, clean_active):
    from tensorflowonspark_tpu.obs import profiler
    metrics.deactivate()
    spans.deactivate()
    t = profiler.StepTimer(warmup=0)
    with t.step(items=1):
      pass
    assert t.summary()["steps"] == 1

  def test_deprecated_import_path_still_works(self):
    import importlib
    import warnings
    with warnings.catch_warnings():
      warnings.simplefilter("ignore", DeprecationWarning)
      import tensorflowonspark_tpu.utils.profiler as shim
      importlib.reload(shim)
    from tensorflowonspark_tpu.obs import profiler as new
    assert shim.StepTimer is new.StepTimer
    assert shim.mfu is new.mfu
    assert shim.trace is new.trace


class TestShipperSamplersAndTopSummary:
  def test_samplers_run_per_ship_and_clock_gauges_land(self):
    """Pre-ship samplers (the device-memory seat) run once per round and
    their gauges — plus the clock-quality gauges — ride the normal delta
    wire into the sink's top summary."""
    sink = collector.ObsSink()
    srv = _SinkServer(sink)
    reg = metrics.MetricsRegistry()
    shipper = collector.ObsShipper(srv.addr, 3, registry=reg,
                                   recorder=spans.SpanRecorder(capacity=4),
                                   interval=60, label="exec")
    calls = []
    shipper.add_sampler(lambda: calls.append(1))

    def broken():
      raise RuntimeError("boom")

    shipper.add_sampler(broken)
    from tensorflowonspark_tpu.obs import device as obs_device
    shipper.add_sampler(obs_device.make_memory_sampler(
        reg, stats_fn=lambda: {"0": {"bytes_in_use": 42,
                                     "peak_bytes_in_use": 64}}))
    try:
      assert shipper.ship(timeout=10)        # ship 1: a TIME exchange too
      # clock-quality gauges PIGGYBACK on real deltas (alone they must
      # not wake the wire); give ship 2 one real counter delta to ride
      reg.counter("work").inc()
      assert shipper.ship(timeout=10)
      assert len(calls) == 2
      assert shipper.sampler_failures == 2   # broken counted, not raised
      top = sink.top_summary()
      entry = top["3"]
      assert entry["label"] == "exec"
      assert entry["metrics"]["device.bytes_in_use"] == 42
      assert entry["metrics"]["device.peak_bytes"] == 64
      assert entry["metrics"]["clock.samples"] >= 1
      assert "clock.rtt_ms" in entry["metrics"]
    finally:
      shipper.stop(timeout=2)
      srv.close()

  def test_health_reply_carries_obs_summary_and_alert_ring(self):
    """The HEALTH verb's PR-8 extension: with a sink and an alert source
    attached, replies carry the per-executor obs summary and the live
    alert ring — the wire tools/obs_top.py monitors through."""
    from tensorflowonspark_tpu.obs import anomaly
    sink = collector.ObsSink()
    sink.ingest({"executor_id": 4, "label": "exec", "pid": 1, "seq": 1,
                 "metrics": {"train.steps": {"type": "counter",
                                             "value": 9.0}},
                 "spans": [], "drops": {}, "clock": {}})
    srv = _SinkServer(sink)
    det = anomaly.AnomalyDetector(sink, registry=metrics.MetricsRegistry(),
                                  recorder=None, interval=1.0, window=4.0)
    det._fire("straggler", 4, 4.0, 100.0, {"rate": 0.0}, "synthetic")
    srv.server.alert_source = det
    try:
      c = rendezvous.Client(srv.addr, timeout=10)
      reply = c._request({"type": "HEALTH"})
      c.close()
      assert reply["type"] == "HEALTH"
      assert reply["obs"]["4"]["metrics"]["train.steps"] == 9.0
      assert [a["alert"] for a in reply["alerts"]] == ["straggler"]
      # json/msgpack-safe end to end (obs_top --once --json prints it)
      json.dumps(reply)
    finally:
      srv.close()

  def test_health_reply_without_obs_stays_liveness_only(self):
    srv = _SinkServer(None)
    try:
      c = rendezvous.Client(srv.addr, timeout=10)
      reply = c._request({"type": "HEALTH"})
      c.close()
      assert "obs" not in reply and "alerts" not in reply
    finally:
      srv.close()


class TestChromeTraceFlows:
  """Cross-process flow arrows (PR 14): spans sharing a request trace id
  chain into chrome flow events (ph s/t/f, one shared id)."""

  def _procs(self):
    # two processes, one request: dispatch on the driver, prefill+decode
    # on the executor — the cross-process waterfall shape
    driver = {"meta": {"label": "driver", "executor_id": 0, "pid": 100},
              "clock": {"offset": 0.0},
              "spans": [{"name": "fleet.dispatch", "ph": "X", "t0": 1.0,
                         "dur": 0.1, "tid": "main", "trace": "aaaa"},
                        {"name": "unrelated", "ph": "X", "t0": 1.0,
                         "dur": 0.1, "tid": "main"}]}
    ex = {"meta": {"label": "exec", "executor_id": 1, "pid": 200},
          "clock": {"offset": 0.0},
          "spans": [{"name": "serve.prefill", "ph": "X", "t0": 1.2,
                     "dur": 0.3, "tid": "loop", "trace": "aaaa"},
                    {"name": "serve.decode.slot", "ph": "X", "t0": 1.6,
                     "dur": 0.2, "tid": "loop", "trace": "aaaa"},
                    {"name": "serve.replay", "ph": "i", "t0": 1.7,
                     "tid": "loop", "trace": "aaaa"}]}
    return [driver, ex]

  def test_flow_chain_is_well_formed(self):
    trace = export.chrome_trace(self._procs())
    flows = [e for e in trace["traceEvents"] if e.get("cat") == "trace"]
    # 3 X-spans on the trace -> s, t, f (instants join via args only)
    assert [f["ph"] for f in sorted(flows, key=lambda e: e["ts"])] \
        == ["s", "t", "f"]
    assert len({f["id"] for f in flows}) == 1
    # flow ids must stay float64-exact: trace viewers parse JSON numbers
    # into doubles, and an id past 2**53 can collide after rounding
    assert all(0 < f["id"] < (1 << 53) for f in flows)
    assert export._flow_id("f" * 16) < (1 << 53)
    assert flows[-1].get("bp") == "e" or \
        next(f for f in flows if f["ph"] == "f")["bp"] == "e"
    # every flow point binds INSIDE its enclosing slice, and the chain
    # crosses the process boundary
    xs = {(e["pid"], e["tid"], e["ts"]): e for e in trace["traceEvents"]
          if e["ph"] == "X" and (e.get("args") or {}).get("trace")}
    assert {f["pid"] for f in flows} == {100, 200}
    for f in flows:
      host = [e for (pid, tid, ts), e in xs.items()
              if pid == f["pid"] and tid == f["tid"]
              and ts <= f["ts"] <= ts + e["dur"]]
      assert host, f
    # the trace id itself is clickable on every span AND the instant
    tagged = [e for e in trace["traceEvents"]
              if (e.get("args") or {}).get("trace") == "aaaa"]
    assert len(tagged) == 4
    json.dumps(trace)

  def test_single_span_traces_emit_no_flow(self):
    procs = [{"meta": {"label": "exec", "executor_id": 0, "pid": 1},
              "clock": {"offset": 0.0},
              "spans": [{"name": "serve.prefill", "ph": "X", "t0": 0.0,
                         "dur": 0.1, "tid": "t", "trace": "bbbb"}]}]
    trace = export.chrome_trace(procs)
    assert [e for e in trace["traceEvents"]
            if e.get("cat") == "trace"] == []

  def test_prometheus_sketch_exposition(self):
    from tensorflowonspark_tpu.obs import quantiles
    sk = quantiles.QuantileSketch()
    sk.extend(float(v) for v in range(1, 101))
    snap = {"serve.ttft_ms": {"type": "sketch", "count": 100,
                              "data": sk.to_dict()}}
    text = export.prometheus_text(snap, labels={"proc": "exec0"})
    lines = text.splitlines()
    assert lines[0] == "# TYPE tos_serve_ttft_ms summary"
    assert 'tos_serve_ttft_ms{proc="exec0",quantile="0.5"} 50' in lines
    assert 'tos_serve_ttft_ms{proc="exec0",quantile="0.99"} 99' in lines
    assert 'tos_serve_ttft_ms_count{proc="exec0"} 100' in lines
