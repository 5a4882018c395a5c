"""Smoke tests for the analytic tools (no hardware, no heavy compute)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class TestServingRoofline:
  def test_ceiling_ordering_and_crossover(self):
    """Decode ceilings must rise monotonically as the cache shrinks
    (mha -> gqa -> mqa, bf16 -> int8) and the context crossover must
    scale inversely with per-step cache bytes."""
    from tools import roofline as rl
    rows = {name: rl.serving_analyze("v5e", 819.0, 8, 2048, kv, cb)
            for name, kv, cb in rl.SERVING_CONFIGS}
    assert (rows["mha_bf16"]["decode_tok_s_ceiling"]
            < rows["gqa4_bf16"]["decode_tok_s_ceiling"]
            < rows["mqa_bf16"]["decode_tok_s_ceiling"])
    assert (rows["mha_bf16"]["decode_tok_s_ceiling"]
            < rows["mha_int8"]["decode_tok_s_ceiling"])
    # int8 halves per-entry cache bytes -> roughly doubles the crossover
    ratio = (rows["mha_int8"]["context_crossover"]
             / rows["mha_bf16"]["context_crossover"])
    assert 1.8 < ratio < 2.2
    # at long context the cache dominates and grouping wins big
    long_mha = rl.serving_analyze("v5e", 819.0, 16, 32768, 12, 2)
    long_gqa8 = rl.serving_analyze("v5e", 819.0, 16, 32768, 4, 1)
    assert (long_gqa8["decode_tok_s_ceiling"]
            > 2.5 * long_mha["decode_tok_s_ceiling"])

  def test_training_analysis_still_runs(self):
    from tools import roofline as rl
    r = rl.analyze({}, "v5e", 819.0)
    assert r["flops_per_step"] > 0 and 0 < r["mfu_serial"] <= 1


class TestServeBenchCompareSmoke:
  @pytest.mark.slow
  def test_compare_smoke_runs_and_holds_parity(self):
    """`serve_bench --compare --smoke` drives the REAL continuous-batching
    engine vs the static fixed-batch loop on CPU: the bench path is
    tier-1-covered (like feed_bench), and the engine's bit-parity with
    single-request decodes is re-verified on every CI run. The speedup
    itself is a chip/shape question the full run answers — the smoke
    shape is dispatch-dominated, so only parity and shape are asserted.

    Marked slow (tier-1 budget audit): ~20 s subprocess, and the prefix
    smoke below gates the same bench path's parity PER STAGE including
    the baseline and full-stack legs — this compare leg is a subset;
    still runs via `make test` / `make serve-bench`."""
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "tools", "serve_bench.py"),
         "--compare", "--smoke"],
        capture_output=True, text=True, timeout=480, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["metric"] == "serving_continuous_vs_static_tokens_per_sec"
    assert result["parity_ok"] is True
    assert result["continuous"]["parity_mismatches"] == 0
    assert result["continuous"]["tok_s"] > 0
    assert result["static"]["tok_s"] > 0
    assert 0.0 < result["continuous"]["occupancy"] <= 1.0
    # static really is the fixed-steps loop: every batch decodes the max
    # budget DRAWN for this workload (a member of the option set — the
    # largest option need not be drawn at every seed)
    assert result["static"]["fixed_steps"] in result["workload"]["budgets"]
    # bench and production share ONE percentile estimator (PR 14): the
    # quantile sketch's p50/p99 agree with the exact sorted list within
    # the sketch's self-reported error bound, gated in the smoke tier
    assert result["sketch_agreement_ok"] is True
    for leg in ("static", "continuous"):
      assert result[leg]["p50_s"] <= result[leg]["p99_s"]


class TestServeBenchPrefixSmoke:
  @pytest.mark.slow  # covered by the serve-bench-prefix target; tier-1 budget
  def test_prefix_workload_smoke_holds_parity_per_stage(self):
    """`serve_bench --prefix-workload --smoke` drives the REAL staged
    decode-speed stack (paged KV at equal HBM, shared-prefix cache,
    self-speculative decode) on CPU: every stage's bit-parity with
    single-request decodes is re-verified on each CI run, the prefix
    cache demonstrably hits, and paging admits more slots at the same
    HBM budget. The ≥1.5× stack speedup is the FULL shape's claim
    (bench_artifacts/serve_bench_prefix.json) — the smoke shape is
    dispatch-dominated, so only parity/shape/mechanism are asserted."""
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "tools", "serve_bench.py"),
         "--prefix-workload", "--smoke"],
        capture_output=True, text=True, timeout=480, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["metric"] == "serving_prefix_stack_tokens_per_sec"
    assert result["parity_ok"] is True
    legs = result["legs"]
    assert set(legs) == {"baseline", "paged", "paged_prefix",
                         "full_stack"}
    for leg in legs.values():
      assert leg["parity_mismatches"] == 0
      assert leg["tok_s"] > 0
    assert legs["paged_prefix"]["prefix_hits"] > 0
    acc = legs["full_stack"].get("spec_accept_rate")
    assert acc is not None and 0.0 <= acc <= 1.0
    slots = result["slots_at_equal_hbm"]
    assert slots["paged"] > slots["contiguous"]


class TestServeBenchChaosSmoke:
  @pytest.mark.slow  # recovery logic unit-tested in test_serving; serve-bench-chaos target
  def test_chaos_smoke_recovers_with_bit_parity(self):
    """`serve_bench --chaos --smoke` injects a REAL deterministic decode
    crash (TOS_CHAOS_SERVE) into the engine mid-workload and measures
    the recovery: tier-1 re-proves on every CI run that crash-replay
    reproduces bit-identical outputs, that the restart actually fired,
    and that recovery latency is measured and bounded."""
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "tools", "serve_bench.py"),
         "--chaos", "--smoke"],
        capture_output=True, text=True, timeout=480, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["metric"] == "serving_chaos_goodput"
    assert result["parity_ok"] is True
    assert result["chaos"]["restarts"] >= 1
    assert result["chaos"]["replays"] >= 1
    assert result["chaos"]["poisoned"] == 0
    assert result["chaos"]["replay_mismatches"] == 0
    assert result["clean"]["tok_s"] > 0 and result["chaos"]["tok_s"] > 0
    assert 0 < result["goodput_ratio"] <= 1.5
    rec = result["recovery_latency_s"]
    assert rec["events"] >= 1 and rec["median"] is not None


class TestServeBenchFleetSmoke:
  @pytest.mark.slow  # make check runs serve-bench-fleet-smoke directly; tier-1 budget
  def test_fleet_smoke_zero_shed_swap_with_bit_parity(self):
    """`serve_bench --fleet --smoke` drives the REAL ServingFleet: N
    replicas behind the router serving the seeded workload with a FULL
    rolling param swap fired mid-run. Tier-1 re-proves on every CI run
    that the swap sheds zero accepted requests, that every replica
    actually swapped, and that fleet outputs stay bit-identical to
    single-request decodes with zero cross-replica replay mismatches."""
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "tools", "serve_bench.py"),
         "--fleet", "--smoke"],
        capture_output=True, text=True, timeout=480, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["metric"] == "serving_fleet_vs_single_tokens_per_sec"
    assert result["parity_ok"] is True
    assert result["zero_shed"] is True
    assert result["fleet"]["swaps"] == result["workload"]["replicas"]
    assert result["fleet"]["shed"] == 0
    assert result["fleet"]["swap_drained_all"] is True
    assert result["fleet"]["replay_mismatches"] == 0
    assert result["single"]["tok_s"] > 0 and result["fleet"]["tok_s"] > 0
    assert result["fleet"]["p99_s"] >= result["fleet"]["p50_s"]


class TestServeBenchFleetCrossHostSmoke:
  @pytest.mark.slow  # make check runs serve-bench-fleet-xhost-smoke directly; tier-1 budget
  def test_cross_host_smoke_parity_swap_and_host_kill_gates(self):
    """`serve_bench --fleet --cross-host --smoke` runs the SAME
    ServingFleet over RemoteReplica proxies whose engines live in
    spawned ServingHost executor processes (registry-built, behind the
    rendezvous wire), paired against the in-process leg on the same
    seeded workload. Gates re-proven here: bit-parity across the
    process boundary, a zero-shed rolling swap over the wire, and the
    TOS_CHAOS_HOST leg where a host is SIGKILLed mid-decode — ejection,
    bit-identical failover replay, then a post-kill zero-shed swap on
    the survivor."""
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "tools", "serve_bench.py"),
         "--fleet", "--cross-host", "--smoke"],
        capture_output=True, text=True, timeout=480, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["metric"] == \
        "serving_fleet_cross_host_vs_in_process_tokens_per_sec"
    assert result["parity_ok"] is True
    assert result["zero_shed"] is True
    assert result["swap_ok"] is True
    assert result["chaos_ok"] is True
    assert result["chaos"]["sigkilled"] is True
    assert result["chaos"]["ejected"] is True
    assert result["chaos"]["failovers"] >= 1
    assert result["chaos"]["shed"] == 0
    assert result["swap"]["swapped"] == result["workload"]["replicas"]
    assert result["in_process"]["tok_s"] > 0
    assert result["cross_host"]["tok_s"] > 0


class TestServeBenchDeploySmoke:
  def test_deploy_smoke_chaos_kill_and_poison_gates(self):
    """`serve_bench --deploy --smoke` drives the REAL continuous-deploy
    loop: registry publish → canary → verify → promote with the
    controller chaos-KILLED at the first promote boundary, then a
    POISONED candidate. Tier-1 re-proves on every CI run the headline
    contract: the kill sheds zero requests, resume() converges every
    replica to ONE consistent version with v2-parity outputs, and the
    poisoned candidate is caught by VERIFY, rolled back bit-identically
    and quarantined — never promoted."""
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "tools", "serve_bench.py"),
         "--deploy", "--smoke"],
        capture_output=True, text=True, timeout=480, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["metric"] == "serving_deploy_canary_rollout"
    assert result["killed_mid_promote"] is True
    assert result["zero_shed"] is True
    assert result["version_consistent"] is True
    assert result["promote_parity"] is True
    assert result["poison_caught_by_verify"] is True
    assert result["rollback_bit_identical"] is True
    assert result["quarantined"] is True
    assert result["never_promoted"] is True
    # the kill landed mid-promote: the fleet really was mixed-version
    assert len(set(result["served_mid_kill"].values())) > 1
    assert result["completed_during_partial_rollout"] \
        == result["workload"]["requests"]
    assert result["fleet_counters"]["shed"] == 0
    assert result["fleet_counters"]["canary_dispatches"] > 0


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


class TestFeedBenchSmoke:
  def test_smoke_runs_end_to_end(self):
    """`feed_bench --smoke` drives the REAL feed plane (hub + ring + jitted
    step) on CPU: the bench path itself is tier-1-covered, so a feed-plane
    regression cannot hide until the next chip window."""
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "tools", "feed_bench.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=480, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["metric"] == "feed_overhead_pct"
    assert result["compute_steps_per_sec"] > 0
    for key in ("queue", "shm", "shm+prefetch"):
      entry = result["per_transport"][key]
      if "error" in entry:        # no native toolchain on this host
        continue
      assert "feed_overhead_pct" in entry
      # per-stage breakdown present and sane
      stages = entry["stages"]
      for stage in ("fetch_s", "decode_s", "assemble_s", "host_batch_s",
                    "wall_s"):
        assert stages[stage] >= 0.0
      # the production path actually went columnar
      assert stages["columnar_chunks"] == stages["chunks"] > 0


class TestTrainBenchSmoke:
  def test_smoke_runs_and_holds_bit_parity(self):
    """`train_bench --smoke` drives the REAL fused train loop
    (make_train_loop + Slab) against the per-step path on CPU: the bench
    path is tier-1-covered and the fusion's bit-identical-trajectory
    contract is re-verified on every CI run. The speedup itself is a
    shape question the full run answers — the smoke shape only asserts
    parity and result shape."""
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "tools", "train_bench.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=480, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["metric"] == "train_fused_speedup"
    assert result["losses_bit_identical"] is True
    assert result["per_step_steps_per_sec"] > 0
    assert result["fused_steps_per_sec"] > 0
    assert result["speedup_median"] > 0
    assert len(result["speedup_reps"]) == result["reps"]
    assert result["unroll"] == 8

  def test_groups_smoke_holds_interchangeability(self):
    """`train_bench --groups --smoke` drives the REAL elastic-groups
    runtime (parallel.groups.GroupSet over a live rendezvous sync plane)
    on CPU: paired no-sync vs synced reps, with the interchangeability
    contract (bit-identical post-sync params across groups) re-verified
    on every CI run. The overhead number is a shape question the full
    `make train-bench-groups` run answers."""
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "tools", "train_bench.py"),
         "--groups", "2", "--smoke"],
        capture_output=True, text=True, timeout=480, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["metric"] == "train_groups_sync_overhead"
    assert result["params_identical_after_sync"] is True
    assert result["groups"] == 2
    assert result["sync_rounds"] > 0
    assert result["nosync_steps_per_sec"] > 0
    assert result["synced_steps_per_sec"] > 0


class TestFeedBenchGraphSmoke:
  @pytest.mark.slow  # make check runs feed-bench-graph-smoke directly; tier-1 budget
  def test_smoke_holds_parity_through_the_autotuned_graph(self):
    """`feed_bench --graph --smoke` drives the REAL datapipe plane on
    CPU: a hub-fed `Dataset.from_feed(...).map(a).map(b).slab(B, K)`
    with the online autotuner live, paired against the fixed-depth
    `_FetchPipeline` baseline. The smoke shape gates the deterministic
    contract (bit-identical loss trajectories across sides) and the
    stall accounting — the >=1.2x speedup is a shape question the full
    `make feed-bench-graph` run answers."""
    import json
    import subprocess
    import sys
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "tools", "feed_bench.py"),
         "--graph", "--smoke"],
        capture_output=True, text=True, timeout=480, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["metric"] == "feed_graph_speedup"
    assert result["deterministic_parity"] is True
    assert result["graph_fetch_dominant_stall_windows"] == 0
    assert result["fixed_rows_per_sec"] > 0
    assert result["graph_rows_per_sec"] > 0
    rep = result["reps"][0]
    assert rep["trajectory_bit_identical"] is True
    # the executor ran as a real multi-stage graph: per-stage runtime
    # summaries for every declared stage, workers/depths all live
    stages = rep["autotune"]["stages"]
    for name in ("src", "map0", "map1", "assemble"):
      assert stages[name]["workers"] >= 1
      assert stages[name]["depth"] >= 1
      assert stages[name]["busy_s"] >= 0.0


class TestFeedBenchWireSmoke:
  def test_smoke_holds_batch_parity_across_wire_legs(self):
    """`feed_bench --wire --smoke` drives the REAL wire plane on CPU:
    four paired queue-transport legs (raw baseline, feeder-side
    pushdown, per-column wire encodings, adaptive envelope budget) plus
    the incompressible probe-cost pair. The smoke shape gates the
    bit-identical-batch contract (every leg's per-batch hashes match)
    and that the heuristic declines float noise — the >=2x bytes/row
    and >=1.2x rows/s numbers are shape questions the full
    `make feed-bench-wire` run answers."""
    import json
    import subprocess
    import sys
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "tools", "feed_bench.py"),
         "--wire", "--smoke"],
        capture_output=True, text=True, timeout=480, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["metric"] == "feed_wire_rows_per_sec"
    assert result["batch_parity"] is True
    rep = result["reps"][0]
    # pushdown delivered fewer wire rows than the raw baseline (the
    # filter ran feeder-side) at fewer bytes per source row
    assert rep["pushdown"]["wire_rows"] < rep["baseline"]["wire_rows"]
    assert rep["pushdown"]["bytes_per_row"] < rep["baseline"][
        "bytes_per_row"]
    # the codec actually engaged on the compressible workload...
    assert any(k != "raw" and v for k, v in rep["compress"]["enc"].items())
    assert rep["compress"]["bytes_per_row"] < rep["pushdown"][
        "bytes_per_row"]
    # ...and declined the incompressible float column (zlib never fires)
    assert rep["incompressible"]["float_column_stayed_raw"] is True
    for leg in ("baseline", "pushdown", "compress", "adaptive"):
      assert result["legs"][leg]["rows_per_sec"] > 0


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


class TestBenchHistory:
  def test_append_check_roundtrip_flags_regression(self, tmp_path):
    from tools import bench_history as bh
    path = str(tmp_path / "history.jsonl")
    for v in (100.0, 102.0, 98.0, 101.0):
      assert bh.append_record("feed_bench", v, "shm-b64", path=path)
    verdicts, regressions = bh.check(path=path, threshold_pct=15.0)
    assert regressions == []
    assert verdicts[0]["verdict"] == "ok"
    # a 30% drop against the trailing median flags
    bh.append_record("feed_bench", 70.0, "shm-b64", path=path)
    verdicts, regressions = bh.check(path=path, threshold_pct=15.0)
    assert len(regressions) == 1
    assert regressions[0]["fingerprint"] == "shm-b64"
    assert regressions[0]["delta_pct"] < -15.0
    # records carry the provenance the satellite asks for
    rec = bh.load(path)[-1]
    assert {"t", "bench", "value", "fingerprint", "rev"} <= set(rec)

  def test_series_are_isolated_by_fingerprint_and_bench(self, tmp_path):
    from tools import bench_history as bh
    path = str(tmp_path / "history.jsonl")
    bh.append_record("feed_bench", 100.0, "shm-b64", path=path)
    bh.append_record("feed_bench", 100.0, "queue-b64", path=path)
    bh.append_record("serve_bench", 50.0, "full-r48", path=path)
    # a huge drop in a DIFFERENT series must not contaminate this one
    bh.append_record("feed_bench", 20.0, "queue-b64", path=path)
    verdicts, regressions = bh.check(path=path, bench="serve_bench")
    assert regressions == []
    assert all(v["bench"] == "serve_bench" for v in verdicts)

  def test_insufficient_history_never_fails(self, tmp_path):
    from tools import bench_history as bh
    path = str(tmp_path / "history.jsonl")
    bh.append_record("feed_bench", 100.0, "solo", path=path)
    verdicts, regressions = bh.check(path=path)
    assert regressions == []
    assert verdicts[0]["verdict"] == "insufficient"
    # missing file: empty, not an error
    assert bh.check(path=str(tmp_path / "nope.jsonl")) == ([], [])

  def test_torn_tail_line_is_skipped(self, tmp_path):
    from tools import bench_history as bh
    path = str(tmp_path / "history.jsonl")
    bh.append_record("feed_bench", 100.0, "shm", path=path)
    with open(path, "a") as f:
      f.write('{"bench": "feed_bench", "val')   # SIGKILL mid-append
    assert len(bh.load(path)) == 1


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
  """The two entry points whose numbers mean "the chip" must fail where
  there is none — never fall back to the CPU and report anyway."""

  REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

  def _run(self, script, extra_env, timeout):
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # what the chip machine would not have either
    for var in ("TOS_TPU_TEST_MODE", "ALLOW_MULTIPLE_LIBTPU_LOAD",
                "TOS_BENCH_SMOKE", "XLA_FLAGS"):
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

  def test_bench_without_tpu_fails_and_names_the_device(self):
    import json
    res = self._run("bench.py", {}, timeout=300)
    assert res.returncode != 0, res.stdout
    line = json.loads(res.stdout.strip().splitlines()[-1])
    assert line["value"] == 0.0 and "no TPU" in line["note"]
    assert (line["platform"], line["device_count"]) == ("cpu", 1)
    assert line["device_kind"]

  def test_bench_smoke_completes_on_cpu_and_names_the_device(self):
    """TOS_BENCH_SMOKE=1 still drives the whole bench path at toy shapes
    on the CPU — the only cover bench.py keeps — and its JSON says which
    device that was, with no MFU (a CPU run has no peak to divide by)."""
    import json
    res = self._run("bench.py", {"TOS_BENCH_SMOKE": "1"}, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    line = json.loads(res.stdout.strip().splitlines()[-1])
    assert (line["platform"], line["device_count"]) == ("cpu", 1)
    assert line["value"] > 0
    extra = line["extra"]
    assert extra["transformer_tokens_per_sec"] > 0
    assert extra["transformer_mfu"] is None
    assert extra["chip_generation"] is None
    assert "banked_measurement" not in extra
    assert "transformer_fallback" not in extra
