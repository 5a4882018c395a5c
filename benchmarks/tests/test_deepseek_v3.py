"""The ``deepseek_v3`` family, its configuration file, its cell, its six
readers and the tool of its mathematics' controls (CPU only:
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``).
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)

from benchmarks.lib import loader  # noqa: E402
from benchmarks.lib import needs_deepseek_v3 as needs  # noqa: E402

CELL = "deepseek-v3-serve-backlog"
COUNTER_READERS = ("decode_step_needed_gb_s.deepseek",
                   "moe_held_assignments_per_token.deepseek",
                   "moe_experts_touched_share.deepseek",
                   "moe_group_hit_share.deepseek", "prefill_tok_s.deepseek")
READERS = COUNTER_READERS + ("decode_attention_roofline.deepseek",)
TRAFFIC = os.path.join(ROOT, "benchmarks", "traffic",
                       "serve-backlog-16k-latent.json")
TRAFFIC_RUNNER = "serve_engine_mean"
FAMILY = os.path.join(ROOT, "benchmarks", "families", "deepseek_v3.py")
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size"]


def _rehearsed(path):
  d = loader.load_json(path)
  return dict({k: v for k, v in d.items() if k != "rehearse"},
              **d["rehearse"])


@pytest.fixture(scope="module")
def toy():
  return _rehearsed(needs.CONFIG)


@pytest.fixture(scope="module")
def fam():
  return loader.load_module("families", "deepseek_v3")


def test_the_tests_copy_of_the_family_is_this_file():
  with open(FAMILY) as a, \
      open(os.path.join(ROOT, "tests", "deepseek_v3_family.py")) as b:
    assert a.read() == b.read()


def test_the_reference_imports_nothing_of_the_program():
  """Only the function of the program half that builds its config names the
  package; the reference expands keys and values a head from the latent and
  absorbs nothing, caches nothing."""
  with open(FAMILY) as f:
    text = f.read()
  lines = [ln for ln in text.splitlines()
           if "import" in ln and "tensorflowonspark_tpu" in ln]
  assert lines == ["  from tensorflowonspark_tpu.models import transformer "
                   "as tfm"]
  reference = text.split("# the plain reference")[1].split(
      "# the program half")[0]
  assert '_mm("bsr,rhk->bshk", c, kvb[:, g], precision)' in reference
  assert "jnp.where(t[None, :] <= at[:, None], scores, -1e30)" in reference
  for word in ("logsumexp", "cached", "q_abs", "decode_attention"):
    assert word not in reference, word


def test_configuration_is_the_catalogs_but_for_the_four_reduced_keys():
  """Every number of the catalog's ``config`` unchanged; ``reduced`` = depth,
  leading dense layers, experts held, vocabulary, with the published counts
  and the 16-chip deployment beside them; each assumption listed, the
  multi-token-prediction block named as left out."""
  c = loader.load_json(needs.CONFIG)
  published = dict(
      hidden_size=7168, num_attention_heads=128, num_key_value_heads=128,
      q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
      qk_rope_head_dim=64, v_head_dim=128, intermediate_size=18432,
      moe_intermediate_size=2048, num_experts_per_tok=8, n_shared_experts=1,
      n_group=8, topk_group=4, routed_scaling_factor=2.5,
      scoring_func="sigmoid", topk_method="noaux_tc", norm_topk_prob=True,
      rope_theta=10000, rms_norm_eps=1e-06, hidden_act="silu",
      attention_bias=False, tie_word_embeddings=False, moe_layer_freq=1,
      num_nextn_predict_layers=1, max_position_embeddings=163840, ep_size=1,
      model_type="deepseek_v3")
  assert {k: c[k] for k in published} == published
  assert c["rope_scaling"] == dict(
      beta_fast=32, beta_slow=1, factor=40, mscale=1, mscale_all_dim=1,
      original_max_position_embeddings=4096, type="yarn")
  catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
  if os.path.exists(catalog):
    with open(catalog) as f:
      row = [r for r in map(json.loads, f) if r["name"] == "DeepSeek-V3"][0]
    assert {k: c[k] for k in row["config"] if k not in REDUCED} \
        == {k: v for k, v in row["config"].items() if k not in REDUCED}
    assert {k: c[k + "_published"] for k in REDUCED} \
        == {k: row["config"][k] for k in REDUCED}
    assert c["source"] == row["source_url"]
  assert {k: c[k] for k in REDUCED} == dict(
      num_hidden_layers=5, first_k_dense_replace=1, n_routed_experts=16,
      vocab_size=16256)
  assert c["vocab_size"] % 128 == 0 \
      and c["vocab_size"] * 8 >= c["vocab_size_published"]
  assert c["n_routed_experts"] * 16 == c["n_routed_experts_published"]
  assert (c["layers_kept"], c["experts_first"]) \
      == ("published layers 0, 3-6", 0)
  entry = [e for e in loader.load_json(os.path.join(ROOT, "BENCHMARK.json"))[
      "configs"] if e["name"] == "deepseek-v3"][0]
  assert entry["reduced"] == c["reduced"] == REDUCED
  assert entry["source"] == c["source"]
  assert "16-chip deployment" in c["deployment"]
  assert c["multi_token_prediction"] == "left out"
  assumed = " ".join(c["assumed"])
  for word in ("multi-token prediction", "LEFT OUT", "half-split",
               "low = floor(d(32)) = 10", "m^2 = 1.87385", "AS ROTATED",
               "selection only", "two largest c", "masked to -inf",
               "routed_scaling_factor 2.5", "shared expert", "no bias",
               "N(0, 1/fan_in)"):
    assert word in assumed, word
  assert c["compute_dtype"] == "bfloat16" and c["float32_activations"] is False


def test_program_tree_is_the_programs_own(fam, toy):
  import jax
  import jax.numpy as jnp
  from flax.core import meta
  from tensorflowonspark_tpu.models import transformer as tfm
  cfg = fam.program_config(toy, 96)
  assert cfg.layer_types == ("mla",) * 3
  assert cfg.ffn_types == ("mlp", "experts", "experts")
  assert (cfg.mla_q_rank, cfg.mla_rope, cfg.experts_groups,
          cfg.experts_groups_kept, cfg.experts_shared) == (24, True, 4, 2, 1)
  want = meta.unbox(jax.eval_shape(lambda: tfm.Transformer(cfg).init(
      jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
  got = fam.program_params(1, toy)
  assert jax.tree.structure(want) == jax.tree.structure(got)
  assert all(a.shape == b.shape for a, b in
             zip(jax.tree.leaves(want), jax.tree.leaves(got)))
  assert fam.param_count(toy) == sum(x.size for x in jax.tree.leaves(got))
  full = loader.load_json(needs.CONFIG)
  assert fam.param_count(full) == full["parameters_as_built"] == 4567097344 \
      == needs.param_count()


def test_the_gates_configuration_is_the_cells(fam):
  """``tools/mosaic_gate.deepseek_cfg`` spells the configuration out by hand;
  it is what the family builds from the configuration and traffic files."""
  from tools import mosaic_gate
  full = {k: v for k, v in loader.load_json(needs.CONFIG).items()
          if k != "rehearse"}
  traffic = loader.load_json(TRAFFIC)
  assert (traffic["slots"], traffic["max_seq"]) == (
      mosaic_gate.DEEPSEEK_SLOTS, mosaic_gate.DEEPSEEK_MAX_SEQ)
  assert fam.program_config(full, traffic["max_seq"]) \
      == mosaic_gate.deepseek_cfg()


def test_forward_matches_reference_in_f32(fam, toy):
  import numpy as np
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import transformer as tfm
  toks = np.random.default_rng(3).integers(0, toy["vocab_size"], (2, 80),
                                           dtype=np.int32)
  cfg = fam.program_config(toy, 96, dtype=jnp.float32)
  out = tfm.Transformer(cfg).apply({"params": fam.program_params(11, toy)},
                                   toks)
  weights = fam.make_weights(11, toy)
  ref = fam.reference_logits(weights, toks, toy)
  # same mathematics, both float32: summation order alone
  assert float(jnp.abs(out - ref).max()) < 2e-4
  # and the reference without a piece of the mathematics is another model
  for control in ("no_group", "no_mscale"):
    less = fam.reference_logits(weights, toks, toy, control)
    assert float(jnp.abs(less - ref).max()) > 0.1, control


def _spec(tmp_path, toy, control=False):
  return dict(cell="test", chips=1, config=toy, traffic=_rehearsed(TRAFFIC),
              seed=5, seconds=1.5, trace=False, rehearse=True,
              control=control, run_dir=str(tmp_path), t_start=0.0)


def test_rehearsal_is_correct_and_control_and_altered_token_are_not(
    tmp_path, monkeypatch, toy):
  """The serve runner's whole child in this process at the cell's rehearsal
  sizes: sound, it is ``correct``, counts group hits beside held assignments
  and latent reads among the cache reads, and writes every leaf through the
  slab in place; the fp8 control's first tokens lie beyond the limit; with
  the served tokens altered where they are produced, ``correct`` comes out
  false."""
  from tensorflowonspark_tpu.serving import slots as slots_lib
  runner = loader.load_module("runners", TRAFFIC_RUNNER)
  spec = _spec(tmp_path, toy, control=True)
  limits = spec["traffic"]["limits"]
  path = os.path.join(str(tmp_path), "sound.json")
  runner.child_main(spec, path)
  rep = loader.load_json(path)
  checks = runner.checks_from(rep, limits)
  assert all(c["ok"] for c in checks) and len(checks) == 5
  assert rep["checked_tokens"] >= 10
  assert rep["control_gap_max"] > limits["served_logit_gap_max"]
  control = runner.checks_from(dict(
      rep, served_gap_max=rep["control_gap_max"],
      served_gap_mean=rep["control_gap_mean"]), limits)
  assert not {c["name"]: c for c in control}["served_logit_gap_mean"]["ok"]
  d = rep["stats_delta"]
  assert 0 < d["moe_group_hits"] < 2 * d["live_slot_steps"]
  assert 0 < d["moe_experts_touched"] <= d["moe_assignments_held"] \
      <= 4 * d["moe_group_hits"]
  assert d["slab_in_place"] == d["slab_dispatches"] > 0
  # 3 layers x one latent leaf written and read, horizon 4, every dispatch
  # (the window may open or close between two counters)
  assert d["cursor_leaf_writes"] % (3 * 4) == 0
  assert abs(d["cursor_leaf_writes"] // (3 * 4) - d["decode_dispatches"]) <= 1
  assert d["decode_attn_reads"] == d["cursor_leaf_writes"]
  assert d["decode_attn_reads_ragged"] == 0                 # the CPU
  assert abs(d["prefill_chunks"] - d["prefills"]) <= 1    # the padded plan
  assert 0 < _read("moe_group_hit_share.deepseek", rep) < 100
  assert 0 < _read("moe_held_assignments_per_token.deepseek", rep) < 2
  assert 0 < _read("moe_experts_touched_share.deepseek", rep) <= 100
  assert _read("prefill_tok_s.deepseek", rep) > 0
  # a step's time on the CPU is no device number: nothing is read from it,
  # and an untraced run has no kernel time
  assert _read("decode_step_needed_gb_s.deepseek", rep) is None
  assert _read("decode_attention_roofline.deepseek", rep) is None
  assert _read("decode_step_needed_gb_s.deepseek", dict(
      rep, device=dict(platform="tpu", kind="TPU v5 lite"))) > 0

  real = slots_lib.SlotDecoder.step_many

  def altered(self, *a, **kw):             # five members: this model counts
    out = real(self, *a, **kw)
    return (out[0], (out[1] + 1) % self.cfg.vocab_size) + tuple(out[2:])

  monkeypatch.setattr(slots_lib.SlotDecoder, "step_many", altered)
  path = os.path.join(str(tmp_path), "broken.json")
  runner.child_main(dict(_spec(tmp_path, toy), seconds=0.1), path)
  rep = loader.load_json(path)
  checks = {c["name"]: c for c in runner.checks_from(rep, limits)}
  assert not checks["served_logit_gap_max"]["ok"], rep["served_gap_max"]


@pytest.mark.parametrize("seed", [7, 2147485999])
def test_the_cells_rehearsal_exits_zero(seed):
  """``benchmarks/run.py --workload <cell> --rehearse`` as a user runs it, on
  two seeds (one past 2**31)."""
  out = subprocess.run(
      [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
       "--workload", CELL, "--rehearse", "--seed", str(seed), "--seconds",
       "2"], env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
      text=True, timeout=600)
  assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
  assert "rehearsal done: correct=True" in out.stdout


def test_the_mathematics_controls_fail_the_rehearsals_limits(monkeypatch):
  """``benchmarks/tools/math_controls.py`` at the rehearsal's sizes (its whole
  ``main``, as the builder calls it on the chip): the sound run is
  ``correct``; the reference WITHOUT the group limit, the one WITHOUT ``m^2``
  and the fp8 one, each in the program's place, are not."""
  from benchmarks.tools import math_controls
  base = loader.load_module("runners", "serve_engine")
  # the tool replaces the runner's reference pass: put it back afterwards
  monkeypatch.setattr(base, "_reference_gaps", base._reference_gaps)
  assert math_controls.main(["--workload", CELL, "--seed", "5", "--seconds",
                             "1.5", "--rehearse"]) == 0
  out = loader.load_json(os.path.join(
      ROOT, "chiprun_out", "sink_control-%s-5.json" % CELL))
  assert out["sound"]["correct"] and not out["sound"]["failed"]
  for control in ("no_group", "no_mscale", "fp8"):
    assert not out[control]["correct"], out[control]
    assert "served_logit_gap_mean" in out[control]["failed"]
    # the group limit moves the held experts' part of some tokens only: the
    # weakest of the three (5.8 times the limit at these sizes, the others 65+)
    assert out[control]["gap_mean"] > (3 if control == "no_group" else 10) \
        * out["limits"]["served_logit_gap_mean_max"]


# -- what a step needs, and the six readers -----------------------------------


def test_needs_against_a_hand_count_at_the_published_sizes():
  """ISSUE 40's hand count: a layer's attention 187,107,328 parameters, the
  dense layer 583,483,392, an expert 44,040,192 = 88.1 MB, an expert layer
  here 937,640,192 (232,997,120 outside its routed experts), embedding, head
  and final norm 233,053,184, the model 4,567,097,344 = 9.13 GB of bf16
  matrices, 3.26 GB of them passed by every token; a token's row 1280 B a
  layer, 6400 B over five; the slab 3.36 GB at 32 x 16384 and 2.52 at 24."""
  z, by_layer = needs.sizes(), needs.layer_params()
  assert by_layer == dict(attention=187107328, dense=583483392,
                          expert_outside_routed=232997120, expert=937640192,
                          ends=233053184)
  assert by_layer["dense"] + 4 * by_layer["expert"] + by_layer["ends"] \
      == needs.param_count() == 4567097344
  assert z["expert_params"] == 44040192 and z["expert_params"] * 2 == 88080384
  assert (z["token_bytes"], z["layers"] * z["token_bytes"]) == (1280, 6400)
  assert (z["latent"], z["latent_values"], z["heads"]) == (576, 512, 128)
  assert needs.slab_bytes(32, 16384) == 3355443200
  assert needs.slab_bytes(24, 16384) == 2516582400
  assert needs.weight_bytes() / 1e9 == pytest.approx(9.15, abs=0.02)
  assert needs.passed_bytes() / 1e9 == pytest.approx(3.26, abs=0.03)
  assert (needs.weight_bytes() - needs.passed_bytes()
          - 2 * z["embed_params"]) == 64 * 88080384            # 5.64 GB
  # a step: passed + 40 touched experts + 24 lanes at a mean cursor of 3400
  step = needs.decode_step_bytes(24, 40, 24 * 3400)
  assert step == needs.passed_bytes() + 40 * 88080384 + (24 * 3400 + 24) * 6400
  # one call of the kernel over 81,600 live rows: bytes against FLOPs at the
  # chip's ridge (240 FLOP a byte): 128 heads x 2 x (576 + 512) / 1280 = 217.6
  assert needs.decode_attention_bytes(81600) == 81600 * 1280
  assert needs.decode_attention_flops(81600) == 81600 * 128 * 2 * 1088
  assert needs.decode_attention_flops(1) / needs.decode_attention_bytes(1) \
      == pytest.approx(217.6)


def _report(**delta):
  d = dict(steps=1000, live_slot_steps=23000, live_context_tokens=78_200_000,
           moe_assignments_held=46000, moe_experts_touched=33000,
           moe_group_hits=45500, t_decode_dispatch_s=2.0, t_decode_fetch_s=10.0,
           decode_attn_reads=5000, decode_attn_reads_ragged=5000)
  d.update(delta)
  return dict(stats_delta=d, device=dict(platform="tpu", kind="TPU v5 lite"),
              trace_summary=dict(kernels={
                  "%decode_attention": dict(seconds=0.060, calls=300.0),
                  "%cursor_write": dict(seconds=0.01, calls=300.0)}),
              requests=[dict(prompt_len=4096, started_at=10.0,
                             prefill_done_at=10.4),
                        dict(prompt_len=512, started_at=11.0,
                             prefill_done_at=11.1),
                        dict(prompt_len=50, started_at=None,
                             prefill_done_at=None)])


def _read(name, report):
  return loader.load_module("layer_metrics", name).read(report)


def test_readers_arithmetic():
  rep = _report()
  assert _read("moe_held_assignments_per_token.deepseek", rep) \
      == pytest.approx(46000 / (23000 * 4)) == 0.5
  assert _read("moe_experts_touched_share.deepseek", rep) \
      == pytest.approx(100 * 33000 / (1000 * 4 * 16))
  assert _read("moe_group_hit_share.deepseek", rep) \
      == pytest.approx(100 * 45500 / (23000 * 4))
  assert _read("prefill_tok_s.deepseek", rep) == pytest.approx(4608 / 0.5)
  nbytes = needs.decode_step_bytes(23, 33, 78200)
  # 12 ms a step on the loop thread's clock: GB a second, no peak in it
  assert _read("decode_step_needed_gb_s.deepseek", rep) \
      == pytest.approx(nbytes / 1e9 / 0.012)
  # 300 calls traced, each over 78,200 live rows: the larger of 1280 B a row
  # at 819 GB/s and 128 x 2 x 1088 FLOP a row at 197 TFLOP/s, over 0.060 s
  least = 300 * 78200 * max(1280 / 819e9, 128 * 2 * 1088 / 197e12)
  assert 1280 / 819e9 > 128 * 2 * 1088 / 197e12            # bytes, just
  assert _read("decode_attention_roofline.deepseek", rep) \
      == pytest.approx(100 * least / 0.060)


def test_the_kernels_roofline_stays_under_100_when_calls_read_whole_blocks():
  """A fixture whose calls ran AT the HBM peak over the bytes they really
  moved: every slot's live rows rounded UP to whole blocks of 128 (a lane at
  3437 rows reads 27 blocks = 3456 rows). The reader counts live rows only,
  so it reads under 100."""
  lanes, cursor, steps = 24, 3437, 1000
  blocks = -(-cursor // 128) * 128
  calls = 5 * 60
  seconds = calls * lanes * blocks * 1280 / 819e9
  rep = _report(steps=steps, live_slot_steps=lanes * steps,
                live_context_tokens=lanes * cursor * steps)
  rep["trace_summary"]["kernels"]["%decode_attention"] = dict(
      seconds=seconds, calls=float(calls))
  got = _read("decode_attention_roofline.deepseek", rep)
  assert 95.0 < got < 100.0, got
  # a device that is not in the table is an error, not a default
  with pytest.raises(ValueError, match="unknown device_kind"):
    _read("decode_attention_roofline.deepseek",
          dict(rep, device=dict(platform="tpu", kind="TPU v9")))


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_from_a_program_without_the_counters(name):
  """The parent of PR 40 has no ``moe_group_hits``, an idle window no step,
  an untraced run no kernel, a program whose latent reads stayed dense no
  ragged read: the reader returns nothing and does not raise."""
  assert _read(name, {}) is None
  assert _read(name, dict(stats_delta=dict(
      steps=8, live_slot_steps=20, live_context_tokens=100,
      moe_assignments_held=5, moe_experts_touched=4, decode_attn_reads=56,
      decode_attn_reads_ragged=56, t_decode_dispatch_s=0.1,
      t_decode_fetch_s=0.1), requests=[])) is None
  idle = _report(steps=0, live_slot_steps=0)
  idle["requests"] = []
  assert _read(name, idle) is None
  if name == "decode_attention_roofline.deepseek":
    assert _read(name, dict(_report(), trace_summary=None)) is None
    assert _read(name, dict(_report(), trace_summary=dict(kernels={}))) is None
    assert _read(name, _report(decode_attn_reads_ragged=0)) is None


def test_the_new_entries_keep_the_contract():
  """Looked up BY NAME: a later PR appends after them."""
  b = loader.load_json(os.path.join(ROOT, "BENCHMARK.json"))
  cell = [w for w in b["workloads"] if w["name"] == CELL]
  assert len(cell) == 1 and cell[0]["chips"] == 1
  assert (cell[0]["config"], cell[0]["traffic"]) == (
      "deepseek-v3", "serve-backlog-16k-latent")
  assert len(cell[0]["why"]) <= 200
  by_name = {m["name"]: m for m in b["per_layer"]}
  for name in READERS:
    m = by_name[name]
    assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
    assert m["layer"] == ("kernels" if "roofline" in name
                          else "model step, serving")
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert os.path.exists(os.path.join(ROOT, "benchmarks", "layer_metrics",
                                       name + ".py"))
  roof = by_name["decode_attention_roofline.deepseek"]
  assert (roof["unit"], roof["source"]) == ("%", "device_trace")
  serve = [m for m in b["end_to_end"] if m["name"] == "serve_tok_s"][0]
  assert CELL in serve["workloads"]
  mimo = {m["name"] for m in b["per_layer"]
          if "mimo-serve-backlog" in m["workloads"]
          and not m["name"].endswith(".mimo")}
  assert len(mimo) == 19
  for name in mimo:
    assert CELL in by_name[name]["workloads"], name
  assert len(b["workloads"]) <= 24 and all(w["chips"] == 1
                                           for w in b["workloads"])


def test_the_traffic_file_is_the_issues():
  t = loader.load_json(TRAFFIC)
  assert (t["runner"], t["loop"], t["max_seq"]) \
      == (TRAFFIC_RUNNER, "closed", 16384)
  # step zero's rule: 32 slots with 64 clients, else 24 with 48
  assert (t["slots"], t["clients"]) in ((32, 64), (24, 48))
  mix = t["mix"]
  mimo = loader.load_json(os.path.join(
      ROOT, "benchmarks", "traffic", "serve-backlog-16k-two-regimes.json"))
  for key in ("prompt_lens", "prompt_weights", "output_lens", "output_weights",
              "pool", "max_total"):
    assert mix[key] == mimo["mix"][key], key        # the MiMo cell's grid
  assert mix["prompt_lens"] == [256, 512, 1024, 2048, 4096, 8192, 12288]
  assert mix["prompt_weights"] == [5, 6, 6, 4, 4, 5, 2]
  assert mix["output_lens"] == [128, 256, 512, 768, 1024]
  assert mix["output_weights"] == [6, 8, 8, 6, 4]
  assert (mix["pool"], mix["max_total"]) == (32, 16384)
  assert (t["ramp_seconds"], t["drain_seconds"], t["check_requests"],
          t["trace_seconds"]) == (20, 0, 8, 3.0)
  from benchmarks.lib import traffic
  pool = traffic.size_pool(mix)
  assert len(pool) == 32 and all(p + o <= 16384 for p, o in pool)
  assert t["rehearse"]["slots"] == 4 and t["rehearse"]["max_seq"] == 96
  assert set(t["limits"]) == {"checked_tokens_min", "served_logit_gap_max",
                              "served_logit_gap_mean_max"}
  for key in ("slots_why", "limits_why", "rehearse_why", "runner_why"):
    assert len(t[key]) > 100 and "TBD" not in t[key], key
