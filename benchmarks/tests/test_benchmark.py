"""Fast CPU tests of the benchmark's own yardstick (run with
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``).

They cover: the trace reduction on a trace recorded on the chip; traffic
determinism and bounds; the metric arithmetic; the gpt2 family against its
plain reference at toy width, and the same comparison FAILING when the
system side is computed in a lower precision (the control) or when the timed
path is broken underneath; ``run.py --rehearse`` end to end for both runners;
and ``BENCHMARK.json`` against the contract's character rules.
"""

import json
import math
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)

from benchmarks.lib import loader, peaks, stats, trace  # noqa: E402
from benchmarks.lib import traffic as traffic_lib  # noqa: E402

TOY = dict(family="gpt2", vocab_size=509, n_positions=128, n_embd=128,
           n_layer=2, n_head=2, n_inner=None)
#: limits at the toy width, set the way PERF.md sets the real ones, from
#: seeds 11-14: the bf16 program reads loss gaps 0.9e-4..2.0e-4, first-moment
#: gaps 1.5e-3..2.5e-3, change gaps 0.6e-3..1.1e-3; the fp8 control reads loss
#: gaps 1.3e-3..2.2e-3 (first moment 4.4e-3..1.2e-2: too close to hold a
#: limit, so the loss is the number the control fails)
TOY_LIMITS = dict(loss_gap_max=6e-4, first_moment_worst_leaf_gap=0.0075,
                  param_change_worst_leaf_gap=0.0035)


def _bench():
  return loader.load_json(os.path.join(ROOT, "BENCHMARK.json"))


# -- trace reduction ---------------------------------------------------------


def test_union_and_gaps():
  iv = [(0, 10), (5, 20), (30, 40), (35, 38)]
  assert trace.union_length(iv) == 30
  assert trace.gaps(iv, 0, 50) == [(20, 30), (40, 50)]


def test_reduce_recorded_trace():
  """A stretch of the train cell's trace as the v5e wrote it (PR 23):
  busy union, idle share and the top operations."""
  ev = loader.load_json(os.path.join(HERE, "trace_fixture.json"))
  events = dict(devices={k: [tuple(e) for e in v]
                         for k, v in ev["devices"].items()},
                host=[tuple(e) for e in ev["host"]])
  s = trace.reduce_events(events)
  assert {lab for lab, _ in s["idle_gaps"]} >= {"feed_wait", "dispatch",
                                                "loss_fetch"}
  assert s["devices"] == 1
  assert 0 < s["busy_s"] <= s["window_s"]
  assert s["idle_share"] == pytest.approx(1 - s["busy_s"] / s["window_s"])
  assert s["busy_s"] == pytest.approx(ev["expect"]["busy_s"], rel=1e-9)
  assert s["window_s"] == pytest.approx(ev["expect"]["window_s"], rel=1e-9)
  assert [n for n, _ in s["device_ops"][:3]] == ev["expect"]["top3"]
  assert len(s["device_ops"]) <= 10 and len(s["idle_gaps"]) <= 10
  # self times never count a stretch twice
  assert sum(s["op_seconds"].values()) <= s["busy_s"] * (1 + 1e-9)
  assert sorted(s["kernels"]) == ev["expect"]["kernels"]
  assert s["kernels"]["%_fwd_impl"]["calls"] == 48


def test_no_device_events_reads_nothing():
  assert trace.reduce_events(dict(devices={}, host=[])) is None


# -- traffic -----------------------------------------------------------------


def _mix():
  return loader.load_json(os.path.join(
      ROOT, "benchmarks", "traffic", "serve-backlog.json"))["mix"]


def test_traffic_same_sizes_for_every_seed_other_order():
  mix = _mix()
  pool = traffic_lib.size_pool(mix)
  assert len(pool) == mix["pool"]
  assert all(16 <= p <= 768 and 16 <= o <= 256 and p + o <= 1024
             for p, o in pool)
  assert 95 <= sorted(p for p, _ in pool)[len(pool) // 2] <= 191
  assert 45 <= sorted(o for _, o in pool)[len(pool) // 2] <= 91

  def take(seed):
    s = traffic_lib.request_stream(mix, seed, 50257)
    return [next(s) for _ in range(len(pool))]

  a, b, c = take(7), take(7), take(2 ** 31 + 5)
  assert all((x[0] == y[0]).all() and x[1] == y[1] for x, y in zip(a, b))
  sizes = lambda reqs: sorted((len(p), o) for p, o in reqs)  # noqa: E731
  assert sizes(a) == sizes(c) == sorted(pool)
  assert [len(p) for p, _ in a] != [len(p) for p, _ in c]
  assert all(0 <= p.min() and p.max() < 50257 for p, _ in a)


def test_arrivals_fixed_count_any_seed():
  a = traffic_lib.poisson_arrivals(5.0, 40.0, 3)
  b = traffic_lib.poisson_arrivals(5.0, 40.0, 2 ** 31 + 9)
  assert len(a) == len(b) == 200 and a == sorted(a) and a != b
  assert 0 <= a[0] and a[-1] < 40.0
  assert a == traffic_lib.poisson_arrivals(5.0, 40.0, 3)


def test_train_rows_differ_and_repeat_per_seed():
  t = traffic_lib.train_table(2 ** 31 + 1, 64, 32, 509)
  assert t.dtype == np.int32 and t.shape == (64, 32) and t.max() < 509
  assert len({r.tobytes() for r in t}) == 64
  assert (t == traffic_lib.train_table(2 ** 31 + 1, 64, 32, 509)).all()
  parts = traffic_lib.train_partitions(t, 48, 3)
  flat = np.stack([r for p in parts for r in p])
  assert (flat == traffic_lib.expected_rows(t, 0, 144)).all()


# -- metric arithmetic -------------------------------------------------------


def test_percentile_and_failed_request_misses():
  assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
  assert stats.percentile(list(range(101)), 95) == 95
  ok = dict(due_at=10.0, first_token_at=10.25, finished_at=11.25,
            out_tokens=11, error=None)
  bad = dict(ok, error="ServingOverloaded")
  assert stats.ttft_ms(ok) == pytest.approx(250.0)
  assert stats.tpot_ms(ok) == pytest.approx(100.0)
  assert stats.ttft_ms(bad) == math.inf and stats.tpot_ms(bad) == math.inf
  # one failure in ten reaches the 95th percentile
  assert stats.percentile([stats.ttft_ms(ok)] * 9 + [stats.ttft_ms(bad)],
                          95) == math.inf
  assert stats.iqr_share([10, 10.1, 9.9, 10.2, 9.8, 10]) < 0.03


def test_peaks_unknown_device_raises():
  assert peaks.chip_peaks("TPU v5 lite")["bf16_flops"] == 197e12
  assert peaks.chip_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
  with pytest.raises(ValueError):
    peaks.chip_peaks("cpu")
  f = peaks.transformer_train_flops_per_token(123551232, 12, 768, 1024)
  assert f == 6 * 123551232 + 6 * 12 * 768 * 1024
  fl, by = peaks.flash_forward_flops_bytes(16, 1024, 12, 64)
  assert fl == 2 * 16 * 12 * 1024 * 1024 * 64 and by == 4 * 16 * 1024 * 768 * 2


# -- the family against its reference, and the controls ----------------------


@pytest.fixture(scope="module")
def toy_train():
  """The program's K=2 fused steps at toy width beside the reference."""
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu.data.readers import Slab
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib
  from tensorflowonspark_tpu.parallel import sharding as SH
  g = loader.load_module("families", "gpt2")
  seed, K, B, S = 11, 2, 4, 64
  slab = np.stack([traffic_lib.train_table(seed, K * B, S, 509)
                   ]).reshape(K, B, S)
  cfg = g.program_config(TOY, S)
  params = g.program_params(seed, TOY)
  p0 = jax.tree.map(jnp.copy, params)
  state = g.program_train_state(params, cfg, S)
  mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=-1),
                             devices=jax.local_devices()[:1])
  loop = SH.make_train_loop(g.program_loss_fn(cfg), mesh, unroll=K)
  state, losses = loop(state, jax.device_put(Slab(slab)))
  prog = dict(mu=g.program_leaf_norms(g.first_moment(state)),
              delta=g.program_leaf_norms(
                  jax.tree.map(jnp.subtract, state.params, p0)))
  w = g.make_weights(seed, TOY)
  ref = g.reference_train(w, slab, TOY, row_block=2)
  return dict(g=g, w=w, slab=slab, ref=ref, program=prog,
              first_losses=[float(x) for x in np.asarray(losses)])


def _train_report(t, program, losses):
  return dict(losses_nonfinite=0, rows_mismatched=0, rows_offered=8,
              rows_seen=8, partial_items=0,
              deliveries=dict(ring=1, queue=0), first_losses=losses,
              program=program, reference=t["ref"])


def _verdict(report):
  runner = loader.load_module("runners", "train_fed")
  checks = runner.checks_from(report, TOY_LIMITS)
  return all(c["ok"] for c in checks), {c["name"]: c for c in checks}


def test_program_tree_is_the_programs_own(toy_train):
  import jax
  import jax.numpy as jnp
  from flax.core import meta
  from tensorflowonspark_tpu.models import transformer as tfm
  g = toy_train["g"]
  cfg = g.program_config(TOY, 64)
  want = meta.unbox(jax.eval_shape(lambda: tfm.Transformer(cfg).init(
      jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
  got = g.program_params(1, TOY)
  assert jax.tree.structure(want) == jax.tree.structure(got)
  assert all(a.shape == b.shape for a, b in
             zip(jax.tree.leaves(want), jax.tree.leaves(got)))
  assert g.param_count(TOY) == sum(x.size for x in jax.tree.leaves(got))


def test_forward_matches_reference_in_f32(toy_train):
  import dataclasses
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import transformer as tfm
  g, w = toy_train["g"], toy_train["w"]
  toks = jnp.asarray(toy_train["slab"][0])
  cfg = dataclasses.replace(g.program_config(TOY, 64), dtype=jnp.float32)
  out = tfm.Transformer(cfg).apply({"params": g.program_params(11, TOY)}, toks)
  ref = g.reference_logits(w, toks, TOY)
  # same mathematics, both float32: only summation order differs
  assert float(jnp.abs(out - ref).max()) < 2e-5


def test_train_correct_for_the_sound_program(toy_train):
  ok, checks = _verdict(_train_report(toy_train, toy_train["program"],
                                      toy_train["first_losses"]))
  assert ok, checks


def test_train_control_fp8_fails(toy_train):
  """The reference in fp8, put in the program's place, is NOT correct."""
  t = toy_train
  low = t["g"].reference_train(t["w"], t["slab"], TOY, precision="fp8",
                               row_block=2)
  ok, checks = _verdict(_train_report(
      t, dict(mu=low["mu"], delta=low["delta"]), low["losses"]))
  assert not ok
  assert not checks["loss_gap_max"]["ok"]


def test_train_broken_paths_fail(toy_train):
  t = toy_train
  # a step that returns its state unchanged: no parameter moved
  frozen = dict(mu=t["program"]["mu"],
                delta={k: 0.0 for k in t["program"]["delta"]})
  ok, checks = _verdict(_train_report(t, frozen, t["first_losses"]))
  assert not ok and not checks["param_change_worst_leaf_gap"]["ok"]
  # a part of the batch left out: the loss is another batch's
  part = t["g"].reference_train(t["w"], t["slab"], TOY, row_block=2,
                                drop_rows=2)
  ok, checks = _verdict(_train_report(t, t["program"], part["losses"]))
  assert not ok and not checks["loss_gap_max"]["ok"]
  # a row that is not the seeded row it has to be
  rep = _train_report(t, t["program"], t["first_losses"])
  rep["rows_mismatched"] = 1
  assert not _verdict(rep)[0]


def _serve_spec(tmp_path, control=False):
  tr = loader.load_json(os.path.join(ROOT, "benchmarks", "traffic",
                                     "serve-backlog.json"))
  tr = dict({k: v for k, v in tr.items() if k != "rehearse"},
            **tr["rehearse"])
  return dict(cell="test", chips=1, config=TOY, traffic=tr, seed=5,
              seconds=1.5, trace=False, rehearse=True, control=control,
              run_dir=str(tmp_path), t_start=0.0)


def test_serve_run_correct_and_altered_token_is_not(tmp_path, monkeypatch):
  """Drives the serve runner's whole child in this process (no look for a
  chip): sound, it is correct and the fp8 control reads wider gaps; with a
  token altered where it is produced, ``correct`` comes out false."""
  from tensorflowonspark_tpu.serving import slots as slots_lib
  runner = loader.load_module("runners", "serve_engine")
  spec = _serve_spec(tmp_path, control=True)
  path = os.path.join(str(tmp_path), "sound.json")
  runner.child_main(spec, path)
  rep = loader.load_json(path)
  limits = spec["traffic"]["limits"]
  assert all(c["ok"] for c in runner.checks_from(rep, limits))
  assert rep["checked_tokens"] >= 10 and rep["stats_delta"]["steps"] > 0
  assert rep["control_gap_max"] > 3 * rep["served_gap_max"]

  real = slots_lib.SlotDecoder.step_many

  def altered(self, *a, **kw):
    slabs, toks, active, remaining = real(self, *a, **kw)
    return slabs, (toks + 1) % self.cfg.vocab_size, active, remaining

  monkeypatch.setattr(slots_lib.SlotDecoder, "step_many", altered)
  path = os.path.join(str(tmp_path), "broken.json")
  runner.child_main(_serve_spec(tmp_path), path)
  checks = {c["name"]: c for c in runner.checks_from(
      loader.load_json(path), limits)}
  assert not checks["served_logit_gap_max"]["ok"]


# -- the harness end to end ---------------------------------------------------


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_rehearse_prints_no_result_line(cell):
  env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
  out = subprocess.run(
      [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
       "--workload", cell, "--seed", str(2 ** 31 + 17), "--seconds", "2",
       "--trace", "1", "--rehearse"],
      capture_output=True, text=True, timeout=280, env=env, cwd=ROOT)
  assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
  assert "rehearsal done: correct=True" in out.stdout
  assert "platform=cpu" in out.stdout
  last = out.stdout.strip().splitlines()[-1]
  assert not last.startswith("{") and '"metrics"' not in out.stdout


def test_benchmark_json_keeps_the_contract():
  b = _bench()
  assert set(b) == {"command", "paths", "run_seconds", "configs",
                    "workloads", "end_to_end", "per_layer"}
  name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
  unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
  cells = [w["name"] for w in b["workloads"]]
  e2e = {m["name"]: m for m in b["end_to_end"]}
  assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
  for group in ("configs", "workloads", "end_to_end", "per_layer"):
    names = [e["name"] for e in b[group]]
    assert len(names) == len(set(names))
    assert all(name.match(n) for n in names), names
  for w in b["workloads"]:
    assert name.match(w["config"]) and name.match(w["traffic"])
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "traffic", w["traffic"] + ".json"))
  for c in b["configs"]:
    assert c["file"].startswith("benchmarks/") and len(c["source"]) <= 200
    assert os.path.exists(os.path.join(ROOT, c["file"]))
  for m in b["end_to_end"] + b["per_layer"]:
    assert unit.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span",
                           "program_counter", "host_clock")
    assert all(w in cells for w in m.get("workloads", []))
  for m in b["end_to_end"]:
    assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock",
                                                          "device_trace")
  for m in b["per_layer"]:
    assert m["moves"] in e2e and 1 <= len(m["layer"]) <= 200
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
    # each listed cell reports the end-to-end metric this one should move
    moved = e2e[m["moves"]]
    assert all(w in moved.get("workloads", cells) for w in m["workloads"])
  for w in cells:     # every cell: setup_s, one more, and a per-layer metric
    assert any(w in m.get("workloads", cells) for m in b["end_to_end"]
               if m["name"] != "setup_s")
    assert any(w in m.get("workloads", cells) for m in b["per_layer"])
  assert len(json.dumps(b)) < 64 * 1024
  run_py = open(os.path.join(ROOT, "benchmarks", "run.py")).read()
  for word in cells + [c["name"] for c in b["configs"]] + list(e2e) + \
      [m["name"] for m in b["per_layer"]] + ["gpt2", "train_fed",
                                              "serve_engine"]:
    if word == "setup_s":
      continue
    assert word not in run_py, "run.py names %r" % word
