"""Online anomaly detection + compile/device tier tests.

Unit level: each detector in ``obs.anomaly`` driven with synthetic
ObsSink aggregates and an injected clock — one firing case and one
just-below-threshold negative case per detector. Device tier: the
jax.monitoring recompile sentinel and the per-seam trace counters,
including THE pin this PR exists for — a steady-state train loop reports
ZERO post-warmup compiles (jit-cache hygiene used to be unpinned and
would regress silently).

Integration (chaos marker): a ``TOS_CHAOS_STALL``-injured executor in a
real 2-process LocalEngine cluster trips the straggler alert, visible in
(a) the supervisor event stream, (b) the driver JSONL via
``obs_report --alerts`` machinery, and (c) the rendezvous HEALTH wire
that ``tools/obs_top.py`` polls.
"""

import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tensorflowonspark_tpu.obs import anomaly, metrics, spans
from tensorflowonspark_tpu.obs import device as obs_device


@pytest.fixture(autouse=True)
def clean_active():
  """No test here may leak the process-global registry/tracer: the
  cluster-driving tests set TOS_OBS=1, which lazily installs both in
  THIS process (the driver side)."""
  yield
  metrics.deactivate()
  spans.deactivate()


class FakeSink(object):
  """The minimal sink surface the detector reads: ``executors`` keys and
  ``metrics(eid)`` snapshots."""

  def __init__(self, eids=(0, 1)):
    self.executors = {e: {} for e in eids}
    self.data = {e: {} for e in eids}

  def metrics(self, eid):
    return self.data[eid]

  def set(self, eid, **values):
    snap = {}
    for name, v in values.items():
      snap[name.replace("__", ".")] = {"type": "counter", "value": float(v)}
    self.data[eid] = snap


def _detector(sink, **kw):
  kw.setdefault("interval", 0.5)
  kw.setdefault("window", 10.0)
  kw.setdefault("registry", metrics.MetricsRegistry())
  kw.setdefault("recorder", None)
  return anomaly.AnomalyDetector(sink, **kw)


class TestStragglerDetector:
  def test_fires_on_slow_executor(self):
    sink = FakeSink()
    det = _detector(sink)
    sink.set(0, train__steps=0)
    sink.set(1, train__steps=0)
    assert det.poll(now=0.0) == []
    sink.set(0, train__steps=100)
    sink.set(1, train__steps=10)          # 90% behind: well past 50%
    alerts = det.poll(now=10.0)
    assert [a["alert"] for a in alerts] == ["straggler"]
    assert alerts[0]["executor_id"] == 1
    assert alerts[0]["evidence"]["cluster_median"] == pytest.approx(10.0)
    # counted into the registry + the bounded ring + the summary
    assert det.recent_alerts()[0]["alert"] == "straggler"
    assert det.summary()["by_kind"] == {"straggler": 1}
    reg_snap = det._reg.snapshot()
    assert reg_snap["obs.alerts"]["value"] == 1
    assert reg_snap["obs.alerts.straggler"]["value"] == 1

  def test_just_below_threshold_stays_quiet(self):
    sink = FakeSink()
    det = _detector(sink)
    sink.set(0, train__steps=0)
    sink.set(1, train__steps=0)
    det.poll(now=0.0)
    sink.set(0, train__steps=100)
    sink.set(1, train__steps=60)          # 40% behind < the 50% threshold
    assert det.poll(now=10.0) == []

  def test_single_executor_never_straggles(self):
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, train__steps=0)
    det.poll(now=0.0)
    sink.set(0, train__steps=0)           # fully stalled — but alone
    assert det.poll(now=10.0) == []

  def test_idle_cluster_rates_are_noise(self):
    """Below MIN_WINDOW_STEPS for the median executor nothing fires —
    a cluster that is barely stepping has no step-rate signal."""
    sink = FakeSink()
    det = _detector(sink)
    sink.set(0, train__steps=0)
    sink.set(1, train__steps=0)
    det.poll(now=0.0)
    sink.set(0, train__steps=3)           # 3 < MIN_WINDOW_STEPS
    sink.set(1, train__steps=0)
    assert det.poll(now=10.0) == []

  def test_cooldown_suppresses_refire(self):
    sink = FakeSink()
    det = _detector(sink)
    det.cooldown = 100.0
    sink.set(0, train__steps=0)
    sink.set(1, train__steps=0)
    det.poll(now=0.0)
    sink.set(0, train__steps=100)
    sink.set(1, train__steps=0)
    assert len(det.poll(now=10.0)) == 1
    sink.set(0, train__steps=200)
    assert det.poll(now=20.0) == []       # inside the cooldown
    sink.set(0, train__steps=2000)
    assert len(det.poll(now=120.0)) == 1  # past it


class TestFeedStallDetector:
  def test_fires_with_stage_attribution(self):
    """Mid-run starvation: batches delivered before, ZERO fresh batches
    across the window, the feed plane dominating it — input-bound."""
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, feed__batches=5, feed__fetch_s=0.0, feed__decode_s=0.0,
             feed__assemble_s=0.0)
    det.poll(now=0.0)
    sink.set(0, feed__batches=5, feed__fetch_s=8.0, feed__decode_s=0.5,
             feed__assemble_s=0.1)
    alerts = det.poll(now=10.0)
    assert [a["alert"] for a in alerts] == ["feed_stall"]
    assert alerts[0]["evidence"]["stage"] == "fetch_s"

  def test_flowing_batches_stay_quiet_despite_fetch_time(self):
    """The fetch PIPELINE thread accrues fetch_s even while batches flow
    (healthy overlap) — seen firing falsely in the bring-up drive; the
    detector must key on zero FRESH batches, not stage seconds alone."""
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, feed__batches=5, feed__fetch_s=0.0, feed__decode_s=0.0,
             feed__assemble_s=0.0)
    det.poll(now=0.0)
    sink.set(0, feed__batches=50, feed__fetch_s=9.5, feed__decode_s=0.5,
             feed__assemble_s=0.1)
    assert det.poll(now=10.0) == []

  def test_below_fraction_stays_quiet(self):
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, feed__batches=5, feed__fetch_s=0.0, feed__decode_s=0.0,
             feed__assemble_s=0.0)
    det.poll(now=0.0)
    sink.set(0, feed__batches=5, feed__fetch_s=5.0, feed__decode_s=0.5,
             feed__assemble_s=0.1)        # 56% < the 60% default
    assert det.poll(now=10.0) == []

  def test_buffered_progress_stays_quiet(self):
    """No fresh batches but the consumer kept stepping on buffered
    chunks: not starved (yet)."""
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, train__steps=10, feed__batches=5, feed__fetch_s=0.0,
             feed__decode_s=0.0, feed__assemble_s=0.0)
    det.poll(now=0.0)
    sink.set(0, train__steps=30, feed__batches=5, feed__fetch_s=9.0,
             feed__decode_s=0.0, feed__assemble_s=0.0)
    assert det.poll(now=10.0) == []

  def test_never_delivered_is_bringup_not_stall(self):
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, feed__batches=0, feed__fetch_s=0.0, feed__decode_s=0.0,
             feed__assemble_s=0.0)
    det.poll(now=0.0)
    sink.set(0, feed__batches=0, feed__fetch_s=9.0, feed__decode_s=0.0,
             feed__assemble_s=0.0)
    assert det.poll(now=10.0) == []

  def test_no_datafeed_executor_is_exempt(self):
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, train__steps=0)           # FILES mode: no feed metrics
    det.poll(now=0.0)
    sink.set(0, train__steps=0)
    assert det.poll(now=10.0) == []

  def test_graph_stage_attribution_names_the_starved_transform(self):
    """Under a ``data.datapipe`` graph the per-stage busy gauges
    (``feed.stage.<name>.busy_s``) join the attribution set: the alert
    must name the dominant GRAPH stage (``pipe:map0``), not just the
    classic fetch/decode/assemble trio (which stay ~zero in graph
    mode)."""
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, feed__batches=5, feed__fetch_s=0.0, feed__decode_s=0.0,
             feed__assemble_s=0.0, feed__stage__src__busy_s=0.0,
             feed__stage__map0__busy_s=0.0,
             feed__stage__assemble__busy_s=0.0)
    det.poll(now=0.0)
    sink.set(0, feed__batches=5, feed__fetch_s=0.1, feed__decode_s=0.0,
             feed__assemble_s=0.0, feed__stage__src__busy_s=0.4,
             feed__stage__map0__busy_s=8.0,
             feed__stage__assemble__busy_s=0.2)
    alerts = det.poll(now=10.0)
    assert [a["alert"] for a in alerts] == ["feed_stall"]
    assert alerts[0]["evidence"]["stage"] == "pipe:map0"

  def test_graph_flowing_batches_stay_quiet_despite_stage_busy(self):
    """Detector negative: a saturated-but-DELIVERING graph stage accrues
    busy seconds by design (that is what the autotuner feeds on) — with
    fresh batches flowing the stall detector must stay quiet."""
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, feed__batches=5, feed__fetch_s=0.0, feed__decode_s=0.0,
             feed__assemble_s=0.0, feed__stage__map0__busy_s=0.0)
    det.poll(now=0.0)
    sink.set(0, feed__batches=60, feed__fetch_s=0.0, feed__decode_s=0.0,
             feed__assemble_s=0.0, feed__stage__map0__busy_s=9.5)
    assert det.poll(now=10.0) == []

  def test_graph_below_fraction_stays_quiet(self):
    """Detector negative: starved window but the graph stages were NOT
    the reason (busy fraction under the threshold — consumer-side
    pause, not an input-bound pipeline)."""
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, feed__batches=5, feed__fetch_s=0.0, feed__decode_s=0.0,
             feed__assemble_s=0.0, feed__stage__src__busy_s=0.0,
             feed__stage__map0__busy_s=0.0)
    det.poll(now=0.0)
    sink.set(0, feed__batches=5, feed__fetch_s=0.0, feed__decode_s=0.0,
             feed__assemble_s=0.0, feed__stage__src__busy_s=2.0,
             feed__stage__map0__busy_s=3.0)   # 50% < the 60% default
    assert det.poll(now=10.0) == []


class TestWindowGuards:
  def test_sub_minimum_window_never_evaluates(self):
    """Startup skew in a sub-second window must not read as a straggler
    (the bring-up drive's false positive: one executor stepped before
    the other's first sample)."""
    sink = FakeSink()
    det = _detector(sink)                 # window 10 → min_span 5
    sink.set(0, train__steps=0)
    sink.set(1, train__steps=0)
    det.poll(now=0.0)
    sink.set(0, train__steps=50)
    sink.set(1, train__steps=0)
    assert det.poll(now=1.0) == []        # span 1 < min_span 5
    assert det.poll(now=6.0) != []        # span 6 ≥ 5: now it's real


class TestRecompileStormDetector:
  def test_fires_after_warmup(self):
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    det.compile_warmup = 5.0
    sink.set(0, xla__compiles=10)
    det.poll(now=0.0)
    sink.set(0, xla__compiles=14)         # 4 >= limit 3, past warmup
    alerts = det.poll(now=10.0)
    assert [a["alert"] for a in alerts] == ["recompile_storm"]
    assert alerts[0]["evidence"]["compiles"] == 4

  def test_warmup_compiles_are_free(self):
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    det.compile_warmup = 60.0
    sink.set(0, xla__compiles=0)
    det.poll(now=0.0)
    sink.set(0, xla__compiles=50)         # inside warmup: expected burst
    assert det.poll(now=10.0) == []

  def test_below_limit_stays_quiet(self):
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    det.compile_warmup = 5.0
    sink.set(0, xla__compiles=10)
    det.poll(now=0.0)
    sink.set(0, xla__compiles=12)         # 2 < limit 3
    assert det.poll(now=10.0) == []


class TestServingSaturationDetector:
  def test_fires_on_saturated_engine(self):
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, serve__queue_depth=0, serve__occupancy=0.5)
    det.poll(now=0.0)
    sink.set(0, serve__queue_depth=12, serve__occupancy=0.97)
    alerts = det.poll(now=10.0)
    assert [a["alert"] for a in alerts] == ["serving_saturated"]

  def test_deep_queue_with_low_occupancy_stays_quiet(self):
    """A deep queue while slots idle is a scheduling bug, not
    saturation — the alert must not cry wolf on it."""
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, serve__queue_depth=0, serve__occupancy=0.5)
    det.poll(now=0.0)
    sink.set(0, serve__queue_depth=12, serve__occupancy=0.5)
    assert det.poll(now=10.0) == []


class TestServeCrashLoopDetector:
  def test_fires_on_restart_burst(self):
    """TOS_OBS_CRASH_LOOP (default 2) engine restarts inside one window
    = a crash loop: one self-heal is routine, repeated ones mean a
    poison request slipped detection or the device is failing."""
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, serve__engine_restarts=1, serve__replays=3)
    det.poll(now=0.0)
    sink.set(0, serve__engine_restarts=3, serve__replays=9)
    alerts = det.poll(now=10.0)
    assert [a["alert"] for a in alerts] == ["serve_crash_loop"]
    assert alerts[0]["evidence"]["restarts"] == 2
    assert alerts[0]["evidence"]["replays"] == 6
    assert alerts[0]["evidence"]["total_restarts"] == 3

  def test_single_recovery_stays_quiet(self):
    """ONE crash-replay inside a window is the self-healing design
    working — just below the threshold, no alert."""
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, serve__engine_restarts=0)
    det.poll(now=0.0)
    sink.set(0, serve__engine_restarts=1)
    assert det.poll(now=10.0) == []

  def test_no_serving_executor_is_exempt(self):
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, train__steps=0)
    det.poll(now=0.0)
    sink.set(0, train__steps=50)
    assert det.poll(now=10.0) == []


class TestKvPagesExhaustedDetector:
  def test_fires_when_pinned_at_zero_with_queue(self):
    """Free pages at 0 for EVERY sample in the window while requests
    queue = the paged KV pool is the admission bottleneck."""
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, serve__kv_pages_free=0, serve__kv_pages_in_use=36,
             serve__queue_depth=5)
    det.poll(now=0.0)
    sink.set(0, serve__kv_pages_free=0, serve__kv_pages_in_use=36,
             serve__queue_depth=7)
    alerts = det.poll(now=10.0)
    assert [a["alert"] for a in alerts] == ["kv_pages_exhausted"]
    assert alerts[0]["evidence"]["queue_depth"] == 7
    assert alerts[0]["evidence"]["pages_in_use"] == 36

  def test_transient_zero_stays_quiet(self):
    """Any sample above 0 inside the window clears the verdict: dipping
    to 0 between completions is the pool doing its job, not exhaustion
    — just below the pinned-all-window threshold."""
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, serve__kv_pages_free=1, serve__kv_pages_in_use=35,
             serve__queue_depth=5)
    det.poll(now=0.0)
    sink.set(0, serve__kv_pages_free=0, serve__kv_pages_in_use=36,
             serve__queue_depth=7)
    assert det.poll(now=10.0) == []

  def test_empty_queue_stays_quiet(self):
    """A full pool with nothing waiting is just a full pool — the alert
    is about ADMISSION being blocked, not utilization."""
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, serve__kv_pages_free=0, serve__kv_pages_in_use=36,
             serve__queue_depth=0)
    det.poll(now=0.0)
    sink.set(0, serve__kv_pages_free=0, serve__kv_pages_in_use=36,
             serve__queue_depth=0)
    assert det.poll(now=10.0) == []

  def test_unpaged_executor_is_exempt(self):
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, serve__queue_depth=9, serve__occupancy=0.2)
    det.poll(now=0.0)
    sink.set(0, serve__queue_depth=9, serve__occupancy=0.2)
    assert det.poll(now=10.0) == []


class TestFleetDetectors:
  def test_degraded_fires_below_full_strength(self):
    """A ServingFleet running fewer active replicas than configured =
    an ejection happened — visible online, not just in the event log."""
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, fleet__replicas_total=3, fleet__replicas_active=3)
    det.poll(now=0.0)
    sink.set(0, fleet__replicas_total=3, fleet__replicas_active=2)
    alerts = det.poll(now=10.0)
    assert [a["alert"] for a in alerts] == ["fleet_degraded"]
    assert alerts[0]["evidence"]["replicas_active"] == 2
    assert alerts[0]["evidence"]["replicas_total"] == 3

  def test_full_strength_stays_quiet(self):
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, fleet__replicas_total=3, fleet__replicas_active=3,
             fleet__queue_depth=0, fleet__occupancy=0.4)
    det.poll(now=0.0)
    sink.set(0, fleet__replicas_total=3, fleet__replicas_active=3,
             fleet__queue_depth=0, fleet__occupancy=0.4)
    assert det.poll(now=10.0) == []

  def test_saturated_fires_scale_up_signal(self):
    """At FULL strength with every replica goodput-bound (the
    serving_saturated thresholds applied to the fleet aggregate), the
    detector says scale up — add a replica."""
    sink = FakeSink(eids=(0,))
    det = _detector(sink)            # queue_sat default 8, per replica
    sink.set(0, fleet__replicas_total=2, fleet__replicas_active=2,
             fleet__queue_depth=16, fleet__occupancy=0.95)
    det.poll(now=0.0)
    sink.set(0, fleet__replicas_total=2, fleet__replicas_active=2,
             fleet__queue_depth=20, fleet__occupancy=0.97)
    alerts = det.poll(now=10.0)
    assert [a["alert"] for a in alerts] == ["fleet_saturated"]
    assert "add a replica" in alerts[0]["message"]

  def test_rolling_swap_in_progress_stays_quiet(self):
    """A DRAINING replica is a healthy operator-initiated swap, not
    lost capacity — firing fleet_degraded on every rolling swap would
    train operators to ignore the real ejection signal (and mid-swap
    saturation readings are suppressed too)."""
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, fleet__replicas_total=3, fleet__replicas_active=2,
             fleet__replicas_draining=1, fleet__queue_depth=99,
             fleet__occupancy=1.0)
    det.poll(now=0.0)
    sink.set(0, fleet__replicas_total=3, fleet__replicas_active=2,
             fleet__replicas_draining=1, fleet__queue_depth=99,
             fleet__occupancy=1.0)
    assert det.poll(now=10.0) == []

  def test_saturated_below_per_replica_queue_stays_quiet(self):
    """Just below the aggregate bound: queue_sat × active − 1."""
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, fleet__replicas_total=2, fleet__replicas_active=2,
             fleet__queue_depth=15, fleet__occupancy=0.97)
    det.poll(now=0.0)
    sink.set(0, fleet__replicas_total=2, fleet__replicas_active=2,
             fleet__queue_depth=15, fleet__occupancy=0.97)
    assert det.poll(now=10.0) == []

  def test_no_fleet_executor_is_exempt(self):
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, serve__queue_depth=9)
    det.poll(now=0.0)
    sink.set(0, serve__queue_depth=9)
    assert det.poll(now=10.0) == []

  def test_degraded_wins_over_saturated(self):
    """A degraded fleet that is ALSO saturated reports degraded — the
    remedy (restore the ejected replica) subsumes the scale-up advice."""
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, fleet__replicas_total=3, fleet__replicas_active=2,
             fleet__queue_depth=99, fleet__occupancy=1.0)
    det.poll(now=0.0)
    sink.set(0, fleet__replicas_total=3, fleet__replicas_active=2,
             fleet__queue_depth=99, fleet__occupancy=1.0)
    alerts = det.poll(now=10.0)
    assert [a["alert"] for a in alerts] == ["fleet_degraded"]


class TestHostLostDetector:
  def test_host_lost_fires_when_a_serving_host_stops_syncing(self):
    """The cross-host serving plane syncing fewer ServingHosts than it
    registered = an executor host died or is partitioned past
    TOS_HOST_TIMEOUT — lost capacity (restore the host), distinct from
    fleet saturation (add a replica)."""
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, serve__hosts_total=2, serve__hosts_alive=2)
    det.poll(now=0.0)
    sink.set(0, serve__hosts_total=2, serve__hosts_alive=1,
             fleet__queue_depth=4, fleet__occupancy=0.8)
    alerts = det.poll(now=10.0)
    assert [a["alert"] for a in alerts] == ["host_lost"]
    assert alerts[0]["evidence"]["hosts_alive"] == 1
    assert alerts[0]["evidence"]["hosts_total"] == 2
    assert "lost capacity" in alerts[0]["message"]

  def test_all_hosts_alive_stays_quiet(self):
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, serve__hosts_total=2, serve__hosts_alive=2)
    det.poll(now=0.0)
    sink.set(0, serve__hosts_total=2, serve__hosts_alive=2)
    assert det.poll(now=10.0) == []

  def test_no_serving_plane_is_exempt(self):
    """Executors without the plane's gauges never trip the detector."""
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, serve__queue_depth=3)
    det.poll(now=0.0)
    sink.set(0, serve__queue_depth=3)
    assert det.poll(now=10.0) == []


class TestGroupDetectors:
  def test_group_lost_fires_below_full_strength(self):
    """An elastic GroupSet running fewer active groups than it has ever
    had = a group died or was evicted (parallel.groups) — surviving
    groups keep stepping degraded, and that must be visible online."""
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, training__groups_total=3, training__groups_active=3)
    det.poll(now=0.0)
    sink.set(0, training__groups_total=3, training__groups_active=2)
    alerts = det.poll(now=10.0)
    assert [a["alert"] for a in alerts] == ["group_lost"]
    assert alerts[0]["evidence"]["groups_active"] == 2
    assert alerts[0]["evidence"]["groups_total"] == 3
    assert "re-admit" in alerts[0]["message"]

  def test_full_group_strength_stays_quiet(self):
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, training__groups_total=3, training__groups_active=3,
             training__sync_ms=40.0)
    det.poll(now=0.0)
    sink.set(0, training__groups_total=3, training__groups_active=3,
             training__sync_ms=40.0)
    assert det.poll(now=10.0) == []

  def test_sync_lag_fires_at_threshold(self):
    """A sync round that ran at/over TOS_OBS_SYNC_LAG_MS means a slow or
    stalled group is dragging every boundary toward the round deadline
    (and past the miss limit the plane will evict it)."""
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    det.sync_lag_ms = 100.0
    sink.set(0, training__groups_total=2, training__groups_active=2,
             training__sync_ms=10.0)
    det.poll(now=0.0)
    sink.set(0, training__groups_total=2, training__groups_active=2,
             training__sync_ms=150.0)
    alerts = det.poll(now=10.0)
    assert [a["alert"] for a in alerts] == ["sync_lag"]
    assert alerts[0]["evidence"]["sync_ms"] == 150.0
    assert alerts[0]["evidence"]["threshold_ms"] == 100.0

  def test_sync_lag_below_threshold_stays_quiet(self):
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    det.sync_lag_ms = 100.0
    sink.set(0, training__groups_total=2, training__groups_active=2,
             training__sync_ms=10.0)
    det.poll(now=0.0)
    sink.set(0, training__groups_total=2, training__groups_active=2,
             training__sync_ms=99.0)
    assert det.poll(now=10.0) == []

  def test_ungrouped_executor_is_exempt(self):
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, train__steps=1)
    det.poll(now=0.0)
    sink.set(0, train__steps=2)
    assert det.poll(now=10.0) == []


class TestMemorySlopeDetector:
  def test_fires_on_monotonic_creep(self):
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    base = 1000 * 1000 * 1000
    fired = []
    for i, t in enumerate((0.0, 3.0, 6.0, 9.0)):
      sink.set(0, device__bytes_in_use=base * (1 + 0.05 * i))
      fired.extend(det.poll(now=t))
    assert [a["alert"] for a in fired] == ["mem_slope"]
    assert fired[0]["evidence"]["growth_pct"] >= 10.0

  def test_below_slope_stays_quiet(self):
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    base = 1000 * 1000 * 1000
    for i, t in enumerate((0.0, 3.0, 6.0, 9.0)):
      sink.set(0, device__bytes_in_use=base * (1 + 0.02 * i))
      alerts = det.poll(now=t)
    assert alerts == []                   # 6% < the 10% default

  def test_peak_then_shrink_is_not_a_leak(self):
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    for v, t in ((100, 0.0), (200, 3.0), (150, 6.0), (160, 9.0)):
      sink.set(0, device__bytes_in_use=v * 1e6)
      alerts = det.poll(now=t)
    assert alerts == []


class TestDetectorPlumbing:
  def test_supervisor_event_mirroring(self):
    class Sup(object):
      def __init__(self):
        self.events = []

      def _event(self, kind, **fields):
        self.events.append(dict(fields, kind=kind))

    sink = FakeSink()
    sup = Sup()
    det = _detector(sink, supervisor=sup)
    sink.set(0, train__steps=0)
    sink.set(1, train__steps=0)
    det.poll(now=0.0)
    sink.set(0, train__steps=100)
    sink.set(1, train__steps=0)
    det.poll(now=10.0)
    assert [e["kind"] for e in sup.events] == ["alert-straggler"]
    assert sup.events[0]["executor_id"] == 1

  def test_jsonl_appends_survive_for_postmortem(self, tmp_path):
    from tensorflowonspark_tpu.obs import export
    sink = FakeSink()
    log = export.ProcessLog(str(tmp_path), label="driver", executor_id=0)
    det = _detector(sink, jsonl=log)
    sink.set(0, train__steps=0)
    sink.set(1, train__steps=0)
    det.poll(now=0.0)
    sink.set(0, train__steps=100)
    sink.set(1, train__steps=0)
    det.poll(now=10.0)
    procs = export.merge_jsonl(export.find_logs(str(tmp_path)))
    assert len(procs) == 1
    assert [a["alert"] for a in procs[0]["alerts"]] == ["straggler"]
    # and the report surfaces the counts (obs_report --alerts machinery)
    from tools import obs_report
    result, _ = obs_report.build_report(str(tmp_path))
    assert result["alerts_total"] == 1
    assert result["alerts_by_kind"] == {"straggler": 1}

  def test_wait_alert_blocks_bounded(self):
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    t0 = time.monotonic()
    assert det.wait_alert(timeout=0.3) is None
    assert time.monotonic() - t0 < 5.0
    sink.set(0, serve__queue_depth=0, serve__occupancy=0.5)
    det.poll(now=0.0)
    sink.set(0, serve__queue_depth=12, serve__occupancy=0.99)
    det.poll(now=10.0)
    got = det.wait_alert(timeout=1.0, kind="serving_saturated")
    assert got and got["alert"] == "serving_saturated"

  def test_eval_failure_counted_not_raised(self):
    class BrokenSink(object):
      executors = {0: {}}

      def metrics(self, eid):
        raise RuntimeError("boom")

    det = _detector(BrokenSink())
    assert det.poll(now=0.0) == []
    assert det.eval_failures == 1

  def test_loop_thread_starts_and_stops(self):
    sink = FakeSink(eids=(0,))
    det = _detector(sink, interval=0.05).start()
    time.sleep(0.2)
    det.stop(timeout=5.0)
    assert det._thread is None


# --- compile/device tier -----------------------------------------------------


class TestDeviceTier:
  def test_note_trace_counts_once_per_jit_cache_entry(self, clean_active):
    import jax
    import jax.numpy as jnp
    reg = metrics.activate()

    def impl(x):
      obs_device.note_trace("unit.seam")
      return x * 2

    fn = jax.jit(impl)
    for _ in range(5):
      fn(jnp.ones((4,)))
    assert reg.snapshot()["xla.compiles.unit.seam"]["value"] == 1
    fn(jnp.ones((8,)))                    # new shape: one more trace
    assert reg.snapshot()["xla.compiles.unit.seam"]["value"] == 2

  def test_monitoring_listener_counts_backend_compiles(self, clean_active):
    import jax
    import jax.numpy as jnp
    reg = metrics.activate()
    if not obs_device.install_compile_listener():
      pytest.skip("jax.monitoring unavailable on this jax")
    before = reg.snapshot().get("xla.compiles", {}).get("value", 0)
    jax.jit(lambda x: x + 1)(jnp.ones((3,)))
    snap = reg.snapshot()
    assert snap["xla.compiles"]["value"] > before
    assert snap["xla.compile_ms"]["count"] >= 1

  def test_steady_state_train_loop_zero_postwarmup_compiles(
      self, clean_active, monkeypatch):
    """THE jit-cache hygiene pin: after warmup, a fixed-shape train loop
    through the real sharded train-step seam must never compile again —
    globally (jax.monitoring) and at the seam (its trace counter)."""
    import jax
    import jax.numpy as jnp
    monkeypatch.setenv(metrics.ENV_OBS, "1")
    reg = metrics.activate()
    obs_device.install_compile_listener()
    obs_device.reset_cost_cache()
    from flax.training import train_state as ts
    import optax
    from tensorflowonspark_tpu.parallel import mesh as mesh_lib
    from tensorflowonspark_tpu.parallel import sharding

    mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=-1),
                               devices=jax.devices()[:1])

    def loss_fn(params, batch):
      pred = batch["x"] @ params["w"]
      return jnp.mean((pred - batch["y"]) ** 2)

    step = sharding.make_train_step(loss_fn, mesh, donate_state=False)
    state = ts.TrainState.create(
        apply_fn=None, params={"w": jnp.ones((4, 2))},
        tx=optax.sgd(1e-2))
    batch = {"x": jnp.ones((8, 4)), "y": jnp.zeros((8, 2))}
    for _ in range(2):                     # warmup: compiles expected
      state, _ = step(state, batch)
    snap = reg.snapshot()
    warm_global = snap.get("xla.compiles", {}).get("value", 0)
    warm_seam = snap["xla.compiles.train.step"]["value"]
    assert warm_seam >= 1
    for _ in range(20):                    # steady state: ZERO compiles
      state, loss = step(state, batch)
    jax.block_until_ready(loss)
    snap = reg.snapshot()
    assert snap.get("xla.compiles", {}).get("value", 0) == warm_global
    assert snap["xla.compiles.train.step"]["value"] == warm_seam
    # the device tier captured the train step's HLO cost exactly once
    assert snap["xla.cost.captures"]["value"] + \
        snap.get("xla.cost.failures", {}).get("value", 0) >= 1

  def test_capture_cost_once_per_shape(self, clean_active):
    import jax
    import jax.numpy as jnp
    reg = metrics.activate()
    obs_device.reset_cost_cache()
    fn = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((16, 16))
    got = obs_device.capture_cost("unit.cost", fn, x)
    if got is None:                        # backend without HLO properties
      assert reg.snapshot()["xla.cost.failures"]["value"] >= 1
      return
    assert got["flops"] > 0
    assert obs_device.capture_cost("unit.cost", fn, x) is None   # memoized
    assert obs_device.capture_cost(
        "unit.cost", fn, jnp.ones((8, 8))) is not None           # new shape
    snap = reg.snapshot()
    assert snap["xla.cost.unit.cost.flops"]["value"] > 0
    assert snap["xla.cost.captures"]["value"] == 2

  def test_memory_sampler_sets_gauges(self):
    reg = metrics.MetricsRegistry()
    fake = {"0": {"bytes_in_use": 100, "peak_bytes_in_use": 150,
                  "bytes_limit": 1000},
            "1": {"bytes_in_use": 50, "peak_bytes_in_use": 80,
                  "bytes_limit": 1000}}
    sampler = obs_device.make_memory_sampler(reg, stats_fn=lambda: fake)
    sampler()
    snap = reg.snapshot()
    assert snap["device.bytes_in_use"]["value"] == 150
    assert snap["device.peak_bytes"]["value"] == 150
    assert snap["device.bytes_limit"]["value"] == 2000
    assert snap["device.mem_samples"]["value"] == 1
    # STATIC memory touches nothing (else the per-round counter bump
    # alone would wake the shipper's idle wire every interval forever)
    sampler()
    assert reg.snapshot()["device.mem_samples"]["value"] == 1
    fake["0"]["bytes_in_use"] = 200                # movement counts again
    sampler()
    assert reg.snapshot()["device.mem_samples"]["value"] == 2
    assert reg.snapshot()["device.bytes_in_use"]["value"] == 250
    # a stats-less backend leaves the gauges untouched
    sampler2 = obs_device.make_memory_sampler(reg, stats_fn=dict)
    sampler2()
    assert reg.snapshot()["device.mem_samples"]["value"] == 2


class TestStragglerBurstTolerance:
  """Fused train loops (make_train_loop) land steps K at a time: an
  executor whose slab dispatch straddles the window edge shows up to one
  burst fewer steps than its peers — quantization, not straggling. The
  detector discounts exactly one burst (the shipped ``train.unroll``
  gauge), no more."""

  def _window(self, det, sink, fast_steps, slow_steps, unroll):
    sink.set(0, train__steps=0, train__unroll=unroll)
    sink.set(1, train__steps=0, train__unroll=unroll)
    det.poll(now=0.0)
    sink.set(0, train__steps=fast_steps, train__unroll=unroll)
    sink.set(1, train__steps=slow_steps, train__unroll=unroll)
    return det.poll(now=10.0)

  def test_behind_by_one_burst_stays_quiet(self):
    """unroll=8, 10 vs 16 steps: 37.5%... below rate threshold needs
    >50% behind — use 6 vs 16 (62.5% behind, past the 50% threshold)
    but with unroll=16 the 10-step gap is within ONE burst: quiet."""
    sink = FakeSink()
    det = _detector(sink)
    assert self._window(det, sink, fast_steps=16, slow_steps=6,
                        unroll=16) == []

  def test_behind_by_more_than_one_burst_fires(self):
    """The just-above pair: the same 62.5%-behind executor with
    unroll=8 is 10 steps behind — more than one burst: fires."""
    sink = FakeSink()
    det = _detector(sink)
    alerts = self._window(det, sink, fast_steps=16, slow_steps=6,
                          unroll=8)
    assert [a["alert"] for a in alerts] == ["straggler"]
    assert alerts[0]["executor_id"] == 1

  def test_no_unroll_metric_means_burst_of_one(self):
    """Per-step clusters (no train.unroll shipped) keep the old
    behavior: any >threshold gap beyond a single step fires."""
    sink = FakeSink()
    det = _detector(sink)
    sink.set(0, train__steps=0)
    sink.set(1, train__steps=0)
    det.poll(now=0.0)
    sink.set(0, train__steps=16)
    sink.set(1, train__steps=6)
    alerts = det.poll(now=10.0)
    assert [a["alert"] for a in alerts] == ["straggler"]


class TestFusedLoopDeviceTier:
  def test_steady_state_fused_loop_zero_postwarmup_compiles(
      self, clean_active, monkeypatch):
    """The fused-loop mirror of THE jit-cache pin: slabs + full-size
    tail batches re-dispatch forever on exactly two cache entries —
    zero post-warmup compiles globally and at both seams."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    monkeypatch.setenv(metrics.ENV_OBS, "1")
    reg = metrics.activate()
    obs_device.install_compile_listener()
    obs_device.reset_cost_cache()
    from flax.training import train_state as ts
    import optax
    from tensorflowonspark_tpu.data.readers import Slab
    from tensorflowonspark_tpu.parallel import mesh as mesh_lib
    from tensorflowonspark_tpu.parallel import sharding

    mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=-1),
                               devices=jax.devices()[:1])

    def loss_fn(params, batch):
      pred = batch["x"] @ params["w"]
      return jnp.mean((pred - batch["y"]) ** 2)

    loop = sharding.make_train_loop(loss_fn, mesh, donate_state=False,
                                    unroll=4)

    def state():
      return ts.TrainState.create(apply_fn=None,
                                  params={"w": jnp.ones((4, 2))},
                                  tx=optax.sgd(1e-2))

    batch = {"x": np.ones((8, 4), "float32"),
             "y": np.zeros((8, 2), "float32")}
    slab = Slab({k: np.stack([v] * 4) for k, v in batch.items()})
    st = state()
    st, _ = loop(st, slab)                 # warmup: both entries compile
    st, _ = loop(st, batch)
    snap = reg.snapshot()
    warm_global = snap.get("xla.compiles", {}).get("value", 0)
    assert snap["xla.compiles.train.loop"]["value"] == 1
    assert snap["xla.compiles.train.step"]["value"] == 1
    for _ in range(10):                    # steady state: ZERO compiles
      st, _ = loop(st, slab)
      st, losses = loop(st, batch)
    jax.block_until_ready(losses)
    snap = reg.snapshot()
    assert snap.get("xla.compiles", {}).get("value", 0) == warm_global
    assert snap["xla.compiles.train.loop"]["value"] == 1
    assert snap["xla.compiles.train.step"]["value"] == 1
    assert snap["train.steps"]["value"] == 4 + 1 + 10 * 5

  def test_cache_hit_not_counted_as_fresh_compile(self, clean_active):
    """Persistent-compile-cache hits fire jax's cache-hit event INSIDE the
    compile-duration region — the paired duration event must count as a
    load (xla.cache_hits), never as a fresh compile, or a relaunched
    executor's warm bring-up reads as a recompile storm."""
    reg = metrics.activate()
    # simulate the exact event order jax emits on a persistent-cache hit
    obs_device._on_event("/jax/compilation_cache/cache_hits")
    obs_device._on_compile_duration(
        "/jax/core/compile/backend_compile_duration", 0.01)
    snap = reg.snapshot()
    assert snap["xla.cache_hits"]["value"] == 1
    assert "xla.compiles" not in snap
    # a duration event WITHOUT a pending hit is a real compile
    obs_device._on_compile_duration(
        "/jax/core/compile/backend_compile_duration", 0.01)
    snap = reg.snapshot()
    assert snap["xla.compiles"]["value"] == 1
    assert snap["xla.cache_hits"]["value"] == 1


# --- chaos integration -------------------------------------------------------


def _straggler_main_fn(args, ctx):
  """ENGINE-mode train loop; the armed executor stalls AFTER its first
  step — the mid-run straggler shape (heartbeats keep flowing from their
  own thread, so liveness stays green while the step rate craters)."""
  import time as _time
  from tensorflowonspark_tpu.obs.profiler import StepTimer
  from tensorflowonspark_tpu.utils import chaos as _chaos

  timer = StepTimer(warmup=0)
  feed = ctx.get_data_feed(train_mode=True)
  step = 0
  while not feed.should_stop():
    batch = feed.next_batch(16)
    if not batch:
      continue
    with timer.step(items=len(batch)):
      sum(batch)
      _time.sleep(0.02)
    step += 1
    ctx.report_progress(step)
    _chaos.stall_point("post-step", index=ctx.executor_id)


@pytest.mark.chaos
def test_chaos_stalled_executor_trips_straggler_alert(tmp_path, monkeypatch):
  """Acceptance path: a TOS_CHAOS_STALL-injured executor trips the
  straggler alert, visible in (a) the supervisor event stream, (b) the
  driver JSONL post-mortem, and (c) the HEALTH wire obs_top polls."""
  from tensorflowonspark_tpu import cluster as tos_cluster
  from tensorflowonspark_tpu.cluster import InputMode
  from tensorflowonspark_tpu.engine import LocalEngine
  from tensorflowonspark_tpu.obs import export
  from tensorflowonspark_tpu.utils import chaos

  chaos.reset()
  obs_dir = str(tmp_path / "obs")
  monkeypatch.setenv(metrics.ENV_OBS, "1")
  monkeypatch.setenv(export.ENV_OBS_DIR, obs_dir)
  monkeypatch.setenv(anomaly.ENV_OBS_DETECT_INTERVAL, "0.25")
  monkeypatch.setenv(anomaly.ENV_OBS_WINDOW, "2.0")
  from tensorflowonspark_tpu.obs import collector
  monkeypatch.setenv(collector.ENV_OBS_INTERVAL, "0.2")

  engine = LocalEngine(
      num_executors=2,
      env={chaos.ENV_STALL: "post-step@1:4",    # executor 1 stalls 4 s
           metrics.ENV_OBS: "1",
           collector.ENV_OBS_INTERVAL: "0.2",
           export.ENV_OBS_DIR: obs_dir})
  try:
    c = tos_cluster.run(engine, _straggler_main_fn,
                        input_mode=InputMode.ENGINE, reservation_timeout=60,
                        heartbeat_interval=0.5)
    assert c.detector is not None
    import threading
    data = list(range(4800))
    feeder = threading.Thread(
        target=lambda: c.train([data[i::8] for i in range(8)],
                               num_epochs=1, feed_timeout=300),
        daemon=True)
    feeder.start()
    # (the detector loop is live) wait for the alert itself, bounded
    alert = c.detector.wait_alert(timeout=60, kind="straggler")
    assert alert is not None, "straggler alert never fired"
    assert alert["executor_id"] == 1

    # (c) the HEALTH wire an out-of-process obs_top would poll
    from tools import obs_top
    reply, client = obs_top.poll_health(tuple(c.server_addr))
    client.close()
    wire_alerts = reply.get("alerts") or []
    assert any(a["alert"] == "straggler" and a["executor_id"] == 1
               for a in wire_alerts), wire_alerts
    snap = obs_top.build_snapshot(reply)
    assert snap["has_alert_ring"] and snap["alerts"]

    feeder.join(timeout=300)
    c.shutdown(timeout=600)

    # (a) the supervisor event stream: alerts land next to recoveries
    kinds = [e["kind"] for e in c.supervisor.events]
    assert "alert-straggler" in kinds, kinds
    # (b) the driver JSONL post-mortem via the obs_report machinery
    from tools import obs_report
    result, procs = obs_report.build_report(obs_dir)
    assert result["alerts_by_kind"].get("straggler", 0) >= 1, result
  finally:
    engine.stop()
    chaos.reset()


class TestCanaryDegradedDetector:
  """``canary_degraded``: the online rollout signal — fires only while a
  canary is actually live (deploy.state at CANARY/VERIFY), on parity
  divergence or a TTFT ratio blowout, keyed per candidate version."""

  def test_parity_divergence_fires(self):
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, deploy__state=2, deploy__candidate=5,
             deploy__parity_failures=0)
    assert det.poll(now=0.0) == []
    sink.set(0, deploy__state=2, deploy__candidate=5,
             deploy__parity_failures=2)
    alerts = det.poll(now=10.0)
    assert [a["alert"] for a in alerts] == ["canary_degraded"]
    assert alerts[0]["evidence"]["candidate"] == 5
    assert alerts[0]["evidence"]["parity_failures"] == 2

  def test_ttft_ratio_fires_with_own_cooldown_key(self):
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, deploy__state=1, deploy__candidate=7,
             deploy__canary_ttft_ratio=1.0)
    assert det.poll(now=0.0) == []
    sink.set(0, deploy__state=1, deploy__candidate=7,
             deploy__canary_ttft_ratio=12.5)   # >= the 10x default
    alerts = det.poll(now=10.0)
    assert [a["alert"] for a in alerts] == ["canary_degraded"]
    assert alerts[0]["evidence"]["ttft_ratio"] == 12.5

  def test_idle_controller_stays_quiet(self):
    # a moving parity counter with NO live canary (state idle) is
    # post-rollback residue, not a new incident
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, deploy__state=0, deploy__candidate=5,
             deploy__parity_failures=0)
    det.poll(now=0.0)
    sink.set(0, deploy__state=0, deploy__candidate=5,
             deploy__parity_failures=3)
    assert det.poll(now=10.0) == []

  def test_below_ratio_stays_quiet(self):
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    sink.set(0, deploy__state=1, deploy__candidate=7,
             deploy__canary_ttft_ratio=1.0)
    det.poll(now=0.0)
    sink.set(0, deploy__state=1, deploy__candidate=7,
             deploy__canary_ttft_ratio=9.9)
    assert det.poll(now=10.0) == []

  def test_deploy_status_surfaces_newest_sample(self):
    sink = FakeSink(eids=(0,))
    det = _detector(sink)
    assert det.deploy_status() is None       # no deploy.* shipped yet
    sink.set(0, deploy__state=1, deploy__version=4, deploy__candidate=5,
             deploy__canary_ttft_ratio=1.2, deploy__canaries=1,
             deploy__promotions=3, deploy__rollbacks=1,
             deploy__parity_failures=0)
    det.poll(now=0.0)
    st = det.deploy_status()
    assert st["state"] == "canary" and st["state_code"] == 1
    assert st["version"] == 4 and st["candidate"] == 5
    assert st["ttft_ratio"] == pytest.approx(1.2)
    assert st["promotions"] == 3 and st["rollbacks"] == 1
