"""Smoke tests for the tools (no hardware, no heavy compute)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class TestObsReportSmoke:
  @pytest.mark.slow  # make check runs obs-smoke directly; tier-1 budget
  def test_smoke_merges_aligned_trace_from_cluster_run(self, tmp_path):
    """`obs_report --smoke` drives a REAL 2-process LocalEngine
    train+inference run with TOS_OBS=1 and merges the per-node JSONL
    logs: the acceptance contract is spans from BOTH executors and the
    driver on one driver-anchored timeline, plus a loadable Chrome
    trace."""
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "tools", "obs_report.py"),
         "--smoke", "--keep", str(tmp_path)],
        capture_output=True, text=True, timeout=480, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["metric"] == "obs_report_smoke"
    assert result["ok"] is True
    assert result["aligned"] is True
    assert result["driver_procs"] >= 1
    assert result["exec_procs"] >= 2
    # spans from the driver AND both executors
    assert result["spans_per_proc"]["driver0"] > 0
    assert result["spans_per_proc"]["exec0"] > 0
    assert result["spans_per_proc"]["exec1"] > 0
    # the instrumented seams actually fired: feed batches, the StepTimer
    # registry seam, and the driver lifecycle spans
    for name in ("feed.batch", "train.step", "cluster.train_feed",
                 "cluster.inference_feed", "cluster.shutdown"):
      assert result["spans_by_name"].get(name, 0) > 0, name
    # the merged Chrome trace is loadable and carries every span
    with open(result["trace_path"]) as f:
      trace = json.load(f)
    assert len(trace["traceEvents"]) >= sum(
        result["spans_per_proc"].values())
    pids = {e["pid"] for e in trace["traceEvents"]}
    assert len(pids) >= 3                  # driver + 2 executors
    # clock offsets were estimated per executor (same-host monotonic
    # clocks are shared, so the estimates must be near zero)
    for proc, off in result["clock_offsets"].items():
      if off is not None:
        assert abs(off) < 0.5, (proc, off)


class TestObsTopSmoke:
  @pytest.mark.slow  # make check runs obs-top-smoke directly; tier-1 budget
  def test_smoke_monitors_live_cluster_through_health_wire(self, tmp_path):
    """`obs_top --smoke` drives a REAL 2-process LocalEngine train run
    and polls it the way an out-of-process monitor would — through the
    rendezvous HEALTH verb: per-executor metrics, a live step rate, and
    the detector's alert ring on the wire."""
    import json
    import subprocess
    import sys
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    keep = str(tmp_path / "frames.txt")
    out = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "tools", "obs_top.py"),
         "--smoke", "--keep", keep],
        capture_output=True, text=True, timeout=480, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["metric"] == "obs_top_smoke"
    assert result["ok"] is True
    assert result["polls"] >= 2
    last = result["last"]
    assert last["has_obs"] and last["has_alert_ring"]
    for eid in ("0", "1"):
      assert last["executors"][eid]["metrics"]["train.steps"] > 0
    # the rendered frames carried the per-executor table
    frames = open(keep).read()
    assert "steps/s" in frames and "exec" in frames


class TestSLOReportSmoke:
  @pytest.mark.slow  # make check runs slo-smoke directly; tier-1 budget
  def test_smoke_links_traces_and_serves_slo_over_health(self, tmp_path):
    """`slo_report --smoke` (make slo-smoke) drives a REAL 2-process
    LocalEngine SERVE run with the obs plane + a declared TTFT objective
    on, and proves the PR-14 acceptance path end to end: SLO status over
    the HEALTH wire mid-run, linked request traces
    (queue→prefill→decode on one trace id) in the merged JSONL, a
    compliant objective table, zero slo_burn on a clean run — then
    `obs_report --request <id>` renders the SAME run's single-request
    waterfall from the kept logs."""
    import json
    import os
    import subprocess
    import sys
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(tools, "slo_report.py"),
         "--smoke", "--keep", str(tmp_path)],
        capture_output=True, text=True, timeout=480, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["metric"] == "slo_report_smoke"
    assert result["ok"] is True
    assert result["full_waterfalls"] > 0
    assert result["slo_burn_alerts"] == 0          # clean run: quiet
    assert "availability" in result["slo_on_wire"]
    assert any(n.startswith("ttft") for n in result["slo_on_wire"])
    by_name = {r["objective"]: r for r in result["objectives"]}
    assert by_name["availability"]["compliant"] is True
    assert by_name["availability"]["events"] == result["rows_served"]
    # chain: the request waterfall renders from the SAME kept logs
    trace_id = result["sample_trace"]
    assert trace_id
    wf_out = subprocess.run(
        [sys.executable, os.path.join(tools, "obs_report.py"),
         str(tmp_path), "--request", trace_id],
        capture_output=True, text=True, timeout=120, env=env)
    assert wf_out.returncode == 0, wf_out.stderr[-2000:]
    wf = json.loads(wf_out.stdout.strip().splitlines()[-1])
    assert wf["metric"] == "obs_request_waterfall"
    assert wf["trace"] == [trace_id]
    for phase in ("serve.queue", "serve.prefill", "serve.prefill.chunk",
                  "serve.decode.slot"):
      assert wf["phases"].get(phase, {}).get("count", 0) > 0, phase
    assert wf["wall_s"] > 0


class TestObsTopSLORow:
  def test_snapshot_carries_slo_and_renders_row(self):
    """The HEALTH-wire SLO payload rides the snapshot verbatim (the
    --once --json contract) and renders as one slo[...] line with the
    burning marker."""
    from tools import obs_top
    slo = {"objectives": [
        {"name": "ttft_p99", "kind": "latency", "observed": 12.0,
         "threshold_ms": 50.0, "burn_fast": 0.2, "burn_slow": 0.1,
         "burning": False},
        {"name": "availability", "kind": "availability",
         "observed": 0.992, "target": 0.999, "burn_fast": 16.0,
         "burn_slow": 15.0, "burning": True}],
        "window_fast": 20.0, "window_slow": 240.0,
        "burn_threshold": 14.4}
    snap = obs_top.build_snapshot({"data": {}, "obs": {}, "alerts": [],
                                   "slo": slo})
    assert snap["slo"] == slo                     # --once --json field
    text = "\n".join(obs_top.render(snap, clear=False))
    assert "slo[" in text
    assert "ttft_p99 12ms/50ms burn 0.2/0.1" in text
    assert "avail 0.9920/0.9990 burn 16.0/15.0 !" in text

  def test_no_slo_on_wire_renders_nothing(self):
    from tools import obs_top
    snap = obs_top.build_snapshot({"data": {}, "obs": {}, "alerts": []})
    assert snap["slo"] is None
    assert "slo[" not in "\n".join(obs_top.render(snap, clear=False))


class TestChipEntryPointsRefuseTheCPU:
  """An entry point whose word means "the chip" must fail where there is
  none, never fall back to the CPU and report anyway (the benchmark's
  entry point is held to the same in tests/test_benchmark_cells.py)."""

  REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

  def _run(self, script, extra_env, timeout):
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # what the chip machine would not have either
    for var in ("TOS_TPU_TEST_MODE", "ALLOW_MULTIPLE_LIBTPU_LOAD",
                "XLA_FLAGS"):
      env.pop(var, None)
    env.update(extra_env)
    return subprocess.run([sys.executable, os.path.join(self.REPO, script)],
                          cwd=self.REPO, env=env, capture_output=True,
                          text=True, timeout=timeout)

  def test_chip_smoke_refuses_the_cpu(self):
    """`python chip_smoke.py` under JAX_PLATFORMS=cpu exits non-zero and
    prints no "ok": true line (the driver runs exactly this in a sandbox
    first, where it MUST fail)."""
    res = self._run("chip_smoke.py", {}, timeout=120)
    assert res.returncode != 0, res.stdout
    assert '"ok": true' not in res.stdout and '"ok":true' not in res.stdout
    assert "no accelerator" in res.stderr
