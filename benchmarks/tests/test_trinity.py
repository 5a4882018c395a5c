"""The ``trinity`` family, its configuration file, its cell and its five
readers (CPU only: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``).
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)

from benchmarks.lib import loader  # noqa: E402
from benchmarks.lib import needs_trinity as needs  # noqa: E402

CELL = "trinity-serve-backlog"
READERS = ("decode_step_needed_gb_s.trinity",
           "moe_held_assignments_per_token.trinity",
           "moe_experts_touched_share.trinity",
           "window_rows_saved_share.trinity", "prefill_tok_s.trinity")
TRAFFIC = os.path.join(ROOT, "benchmarks", "traffic",
                       "serve-backlog-16k.json")
#: ``serve_engine`` and the mean gap's check (``runners/serve_engine_mean.py``)
TRAFFIC_RUNNER = "serve_engine_mean"
KIB = 1024


def _rehearsed(path):
  d = loader.load_json(path)
  return dict({k: v for k, v in d.items() if k != "rehearse"},
              **d["rehearse"])


@pytest.fixture(scope="module")
def toy():
  return _rehearsed(needs.CONFIG)


@pytest.fixture(scope="module")
def fam():
  return loader.load_module("families", "trinity")


def test_the_tests_copy_of_the_family_is_this_file():
  with open(os.path.join(ROOT, "benchmarks", "families", "trinity.py")) as a, \
      open(os.path.join(ROOT, "tests", "trinity_family.py")) as b:
    assert a.read() == b.read()


def test_the_reference_imports_nothing_of_the_program():
  """Only the function of the program half that builds its config names the
  package."""
  with open(os.path.join(ROOT, "benchmarks", "families", "trinity.py")) as f:
    lines = [ln for ln in f.read().splitlines()
             if "import" in ln and "tensorflowonspark_tpu" in ln]
  assert lines == ["  from tensorflowonspark_tpu.models import transformer "
                   "as tfm"]


def test_configuration_is_the_catalogs_but_for_the_four_reduced_keys():
  """Every width of the catalog's ``config`` unchanged; ``reduced`` = depth,
  dense layers, experts held, vocabulary, with the published counts and the
  deployment beside them; each assumption listed."""
  c = loader.load_json(needs.CONFIG)
  reduced = ["num_hidden_layers", "num_dense_layers", "num_experts",
             "vocab_size"]
  published = dict(
      hidden_size=3072, num_attention_heads=48, num_key_value_heads=8,
      head_dim=128, intermediate_size=12288, moe_intermediate_size=3072,
      num_experts_per_tok=4, num_shared_experts=1, score_func="sigmoid",
      route_norm=True, route_scale=2.448, n_group=1, topk_group=1,
      sliding_window=4096, rope_theta=10000, rope_scaling=None,
      rms_norm_eps=1e-05, hidden_act="silu", mup_enabled=True,
      tie_word_embeddings=False, max_position_embeddings=262144,
      model_type="afmoe")
  assert {k: c[k] for k in published} == published
  assert c["layer_types"] == (["sliding_attention"] * 3
                              + ["full_attention"]) * 15
  catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
  if os.path.exists(catalog):
    with open(catalog) as f:
      row = [r for r in map(json.loads, f)
             if r["name"] == "Trinity-Large-Preview"][0]
    assert {k: c[k] for k in row["config"] if k not in reduced} \
        == {k: v for k, v in row["config"].items() if k not in reduced}
    assert {k: c[k + "_published"] for k in reduced} \
        == {k: row["config"][k] for k in reduced}
    assert c["source"] == row["source_url"]
  assert {k: c[k] for k in reduced} == dict(
      num_hidden_layers=5, num_dense_layers=1, num_experts=32,
      vocab_size=25024)
  assert c["vocab_size"] * 8 == c["vocab_size_published"]
  assert c["num_experts"] * 8 == c["num_experts_published"]
  assert (c["first_layer_published"], c["layers_kept"], c["experts_first"]) \
      == (6, "published layers 6-10", 0)
  entry = [e for e in loader.load_json(os.path.join(ROOT, "BENCHMARK.json"))[
      "configs"] if e["name"] == "trinity-large-preview"][0]
  assert entry["reduced"] == c["reduced"] == reduced
  assert entry["source"] == c["source"]
  assert "8-chip deployment" in c["deployment"]
  assumed = " ".join(c["assumed"])
  for word in ("output gate", "RMSNorm over each head", "SLIDING layers only",
               "four RMSNorms", "sqrt(hidden_size)", "no bias",
               "selection only", "half-split", "RING", "N(0, 1/fan_in)"):
    assert word in assumed, word
  assert c["compute_dtype"] == "bfloat16" and "float32_activations" not in c


def test_program_tree_is_the_programs_own(fam, toy):
  import jax
  import jax.numpy as jnp
  from flax.core import meta
  from tensorflowonspark_tpu.models import transformer as tfm
  cfg = fam.program_config(toy, 96)
  assert cfg.layer_windows == (8, 8, 0, 8, 8)
  assert cfg.layer_rope == (True, True, False, True, True)
  assert cfg.ffn_types == ("mlp",) + ("experts",) * 4
  assert (cfg.qk_norm, cfg.attn_gate, cfg.post_norm, cfg.embed_scale) \
      == (True, True, True, 8.0)
  want = meta.unbox(jax.eval_shape(lambda: tfm.Transformer(cfg).init(
      jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
  got = fam.program_params(1, toy)
  assert jax.tree.structure(want) == jax.tree.structure(got)
  assert all(a.shape == b.shape for a, b in
             zip(jax.tree.leaves(want), jax.tree.leaves(got)))
  assert fam.param_count(toy) == sum(x.size for x in jax.tree.leaves(got))
  full = loader.load_json(needs.CONFIG)
  assert fam.param_count(full) == full["parameters_as_built"] == 4321903872


def test_the_gates_configuration_is_the_cells(fam):
  """``tools/mosaic_gate.trinity_cfg`` spells the configuration out by hand;
  it is what the family builds from the configuration and traffic files."""
  from tools import mosaic_gate
  full = {k: v for k, v in loader.load_json(needs.CONFIG).items()
          if k != "rehearse"}
  traffic = loader.load_json(TRAFFIC)
  assert (traffic["slots"], traffic["max_seq"]) == (
      mosaic_gate.TRINITY_SLOTS, mosaic_gate.TRINITY_MAX_SEQ)
  assert fam.program_config(full, traffic["max_seq"]) \
      == mosaic_gate.trinity_cfg()


def test_forward_matches_reference_in_f32(fam, toy):
  import numpy as np
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import transformer as tfm
  toks = np.random.default_rng(3).integers(0, toy["vocab_size"], (2, 48),
                                           dtype=np.int32)
  cfg = fam.program_config(toy, 96, dtype=jnp.float32)
  out = tfm.Transformer(cfg).apply({"params": fam.program_params(11, toy)},
                                   toks)
  ref = fam.reference_logits(fam.make_weights(11, toy), toks, toy)
  # same mathematics, both float32: summation order alone
  assert float(jnp.abs(out - ref).max()) < 2e-4


def _spec(tmp_path, toy, control=False):
  return dict(cell="test", chips=1, config=toy, traffic=_rehearsed(TRAFFIC),
              seed=5, seconds=1.5, trace=False, rehearse=True,
              control=control, run_dir=str(tmp_path), t_start=0.0)


def test_rehearsal_is_correct_and_control_and_altered_token_are_not(
    tmp_path, monkeypatch, toy):
  """The serve runner's whole child in this process at the cell's rehearsal
  sizes: sound, it is ``correct``, counts window rows beside context rows
  and writes every leaf through the slab in place; the fp8 control's first
  tokens lie beyond the limit; with the served tokens altered where they are
  produced, ``correct`` comes out false."""
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
  # the control in the program's place is NOT correct, by the mean's limit
  control = runner.checks_from(dict(
      rep, served_gap_max=rep["control_gap_max"],
      served_gap_mean=rep["control_gap_mean"]), limits)
  assert not {c["name"]: c for c in control}["served_logit_gap_mean"]["ok"]
  d = rep["stats_delta"]
  assert 0 < d["window_context_tokens"] < d["live_context_tokens"]
  assert d["window_context_tokens"] <= 8 * d["live_slot_steps"]
  assert d["moe_assignments_held"] > 0 and d["moe_experts_touched"] > 0
  assert d["slab_in_place"] == d["slab_dispatches"] > 0
  # 5 layers x (K, V) leaves and 5 reads, horizon 4, every dispatch (the
  # window may open or close between the two counters of one dispatch)
  assert d["cursor_leaf_writes"] % (10 * 4) == 0
  assert abs(d["cursor_leaf_writes"] // (10 * 4)
             - d["decode_dispatches"]) <= 1
  assert d["decode_attn_reads"] * 2 == d["cursor_leaf_writes"]
  assert abs(d["prefill_chunks"] - d["prefills"]) <= 1    # the padded plan
  assert 0 < _read("window_rows_saved_share.trinity", rep) < 100
  assert 0 < _read("moe_held_assignments_per_token.trinity", rep) < 2
  assert 0 < _read("moe_experts_touched_share.trinity", rep) <= 100
  assert _read("prefill_tok_s.trinity", rep) > 0
  # a step's time on the CPU is no device number: nothing is read from it
  assert _read("decode_step_needed_gb_s.trinity", rep) is None
  assert _read("decode_step_needed_gb_s.trinity", dict(
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


# -- what a step needs, and the five readers ----------------------------------


def test_needs_against_a_hand_count_at_the_published_sizes():
  """Attention 3 x 3072 x 6144 (q, gate, out) + 2 x 3072 x 1024 (k, v) =
  62,914,560 bf16 numbers a layer and 4 x 3072 + 2 x 128 float32 scales; an
  expert 3 x 3072 x 3072 = 28,311,552 = 56.6 MB; the router 3072 x 256 + 256
  float32; the dense MLP 3 x 3072 x 12288; embedding and head 25024 x 3072
  each. As built 4,321,903,872 parameters = 8.64 GB; 1.24 GB outside the
  routed experts a decode step reads; a token 4 KiB a layer; the slab 3.22
  GB at 24 x 16384 where five whole-context leaf pairs would be 8.05."""
  z = needs.sizes()
  attention, expert = 3 * 3072 * 6144 + 2 * 3072 * 1024, 3 * 3072 * 3072
  assert (attention, expert) == (62914560, 28311552)
  assert z["expert_params"] == expert and expert * 2 == 56623104
  assert (z["layers"], z["window_layers"], z["full_layers"],
          z["expert_layers"], z["held"], z["window"]) == (5, 4, 1, 4, 32, 4096)
  assert z["token_bytes"] == 2 * 8 * 128 * 2 == 4 * KIB
  dense = 5 * attention + 4 * expert + 3 * 3072 * 12288 + 3072 * 25024
  f32 = 5 * (4 * 3072 + 256) + 3072 + 4 * (3072 + 1) * 256
  assert (z["dense_params"], z["f32_params"]) == (dense, f32)
  params = dense + f32 + 25024 * 3072 + 4 * 32 * expert
  assert params == 4321903872 \
      == loader.load_json(needs.CONFIG)["parameters_as_built"]
  assert needs.weight_bytes() == (params - f32) * 2 + f32 * 4
  assert 8.64e9 < needs.weight_bytes() < 8.66e9
  outside = dense * 2 + f32 * 4
  assert 1.23e9 < outside < 1.25e9
  assert needs.slab_bytes(24, 16384) == 24 * 4 * KIB * (16384 + 4 * 4096)
  assert 3.2e9 < needs.slab_bytes(24, 16384) < 3.25e9
  assert 8.0e9 < 5 * 24 * 16384 * 4 * KIB < 8.1e9
  # 24 live lanes at 5000 positions each, 40 experts touched: the weights
  # outside the experts, 40 experts, the context in the full layer and the
  # window's 4096 rows of it in four, 24 rows written in five
  got = needs.decode_step_bytes(24, 40, 24 * 5000, 24 * 4096)
  assert got == outside + 40 * expert * 2 \
      + 4 * KIB * (24 * 5000 + 4 * 24 * 4096) + 24 * 4 * KIB * 5
  assert 5.5e9 < got < 5.7e9
  # the sizes are the configuration file's own: a window of 2048 halves what
  # the slab's rings hold
  half = dict(loader.load_json(needs.CONFIG), sliding_window=2048)
  assert needs.slab_bytes(24, 16384, half) \
      == 24 * 4 * KIB * (16384 + 4 * 2048)


def _report(**delta):
  d = dict(steps=1000, live_slot_steps=23000, live_context_tokens=92_000_000,
           window_context_tokens=62_000_000, moe_assignments_held=46000,
           moe_experts_touched=40000, t_decode_dispatch_s=2.0,
           t_decode_fetch_s=10.0)
  d.update(delta)
  return dict(stats_delta=d, device=dict(platform="tpu", kind="TPU v5 lite"),
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
  assert _read("moe_held_assignments_per_token.trinity", rep) \
      == pytest.approx(46000 / (23000 * 4)) == 0.5
  assert _read("moe_experts_touched_share.trinity", rep) \
      == pytest.approx(100 * 40000 / (1000 * 4 * 32))
  assert _read("window_rows_saved_share.trinity", rep) \
      == pytest.approx(100 * (1 - 62 / 92))
  assert _read("window_rows_saved_share.trinity", _report(
      window_context_tokens=92_000_000)) == 0.0
  assert _read("prefill_tok_s.trinity", rep) == pytest.approx(4608 / 0.5)
  nbytes = needs.decode_step_bytes(23, 40, 92000, 62000)
  # 12 ms a step on the loop thread's clock: GB a second, no peak in it
  assert _read("decode_step_needed_gb_s.trinity", rep) \
      == pytest.approx(nbytes / 1e9 / 0.012)


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_from_a_program_without_the_counters(name):
  """The parent of PR 32 has neither the rings nor their counter, and an
  idle window no step: the reader returns nothing and does not raise."""
  assert _read(name, {}) is None
  assert _read(name, dict(stats_delta=dict(
      steps=8, live_slot_steps=20, live_context_tokens=100,
      moe_assignments_held=5, moe_experts_touched=4,
      t_decode_dispatch_s=0.1, t_decode_fetch_s=0.1), requests=[])) is None
  idle = _report(steps=0, live_slot_steps=0)
  idle["requests"] = []
  assert _read(name, idle) is None


def test_the_new_entries_keep_the_contract():
  """Looked up BY NAME: a later PR appends after them."""
  b = loader.load_json(os.path.join(ROOT, "BENCHMARK.json"))
  cell = [w for w in b["workloads"] if w["name"] == CELL]
  assert len(cell) == 1 and cell[0]["chips"] == 1
  assert (cell[0]["config"], cell[0]["traffic"]) == (
      "trinity-large-preview", "serve-backlog-16k")
  assert len(cell[0]["why"]) <= 200
  by_name = {m["name"]: m for m in b["per_layer"]}
  for name in READERS:
    m = by_name[name]
    assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
    assert m["layer"] == "model step, serving"
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
  serve = [m for m in b["end_to_end"] if m["name"] == "serve_tok_s"][0]
  assert CELL in serve["workloads"]
  for name in (
      "compile_s", "cache_hits", "slot_occupancy", "decode_step_ms",
      "device_idle_share.backlog", "decode_step_inner_ms.backlog",
      "loop_host_share.backlog", "slab_in_place_share.backlog",
      "cursor_write_dma_share.backlog", "decode_attn_ragged_share.backlog",
      "prefill_chunks_per_prompt.backlog", "prefill_pad_share.backlog"):
    assert CELL in by_name[name]["workloads"], name
  assert len(b["workloads"]) <= 24 and all(w["chips"] == 1
                                           for w in b["workloads"])


def test_the_mean_limit_lies_between_the_two_readings():
  """``serve_engine_mean`` is ``serve_engine`` and the mean's check. The
  limit on the chip (my chip runs, PR 32: the largest mean of 19 sound runs,
  the smallest of 6 fp8 controls) has room on both sides, which the limit on
  the maximum cannot have (the sound runs' largest maximum lies above five of
  the control's seven)."""
  t = loader.load_json(TRAFFIC)
  base = loader.load_module("runners", "serve_engine")
  runner = loader.load_module("runners", TRAFFIC_RUNNER)
  assert runner.child_main is base.child_main
  limit = t["limits"]["served_logit_gap_mean_max"]
  sound_largest, control_smallest = 0.004407, 0.045009
  assert limit / sound_largest > 3 and control_smallest / limit > 3
  assert 1.160 > 0.925             # the maxima: sound largest, control smallest
  rep = dict(stats_all=dict(engine_restarts=0, replay_mismatches=0),
             checked_tokens=2000, served_gap_max=1.16,
             served_gap_mean=sound_largest)
  assert all(c["ok"] for c in runner.checks_from(rep, t["limits"]))
  names = [c["name"] for c in runner.checks_from(rep, t["limits"])]
  assert names == [c["name"] for c in base.checks_from(rep, t["limits"])] + [
      "served_logit_gap_mean"]
  for mean in (control_smallest, None):
    bad = {c["name"]: c for c in runner.checks_from(
        dict(rep, served_gap_mean=mean), t["limits"])}
    assert not bad["served_logit_gap_mean"]["ok"]
    assert bad["served_logit_gap_max"]["ok"]     # the maximum alone passes it


def test_the_traffic_file_is_the_issues():
  t = loader.load_json(TRAFFIC)
  assert (t["runner"], t["loop"], t["slots"], t["clients"], t["max_seq"]) \
      == (TRAFFIC_RUNNER, "closed", 24, 48, 16384)
  mix = t["mix"]
  assert mix["prompt_lens"] == [256, 512, 1024, 2048, 4096, 6144, 8192, 12288]
  assert mix["prompt_weights"] == [3, 5, 6, 6, 5, 4, 3, 2]
  assert mix["output_lens"] == [128, 192, 256, 384, 512]
  assert mix["output_weights"] == [4, 6, 6, 5, 3]
  assert (mix["pool"], mix["mix_seed"], mix["max_total"]) == (34, 2601, 16384)
  assert (t["ramp_seconds"], t["drain_seconds"], t["check_requests"],
          t["trace_seconds"]) == (20, 0, 8, 3.0)
  from benchmarks.lib import traffic
  pool = traffic.size_pool(mix)
  assert len(pool) == 34 and all(p + o <= 16384 for p, o in pool)
  assert sum(p >= 4096 for p, _ in pool) == 14
  assert sum(p for p, _ in pool) / 34 == pytest.approx(3411, abs=1)
  assert t["rehearse"]["slots"] == 4 and t["rehearse"]["max_seq"] == 96
  # the rehearsal's prompts pass its window of 8 and its ring of 16 rows
  assert max(t["rehearse"]["mix"]["prompt_lens"]) > 16
