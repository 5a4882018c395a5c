"""The ``mimo_v2_flash`` family, its configuration file, its cell, its six
readers and the dropped-sink control's tool (CPU only: ``JAX_PLATFORMS=cpu
python -m pytest benchmarks/tests -q``).
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
from benchmarks.lib import needs_mimo_v2_flash as needs  # noqa: E402

CELL = "mimo-serve-backlog"
COUNTER_READERS = ("decode_step_needed_gb_s.mimo",
                   "moe_held_assignments_per_token.mimo",
                   "moe_experts_touched_share.mimo",
                   "window_rows_saved_share.mimo", "prefill_tok_s.mimo")
READERS = COUNTER_READERS + ("decode_attention_roofline.mimo",)
TRAFFIC = os.path.join(ROOT, "benchmarks", "traffic",
                       "serve-backlog-16k-two-regimes.json")
TRAFFIC_RUNNER = "serve_engine_mean"
FAMILY = os.path.join(ROOT, "benchmarks", "families", "mimo_v2_flash.py")
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]


def _rehearsed(path):
  d = loader.load_json(path)
  return dict({k: v for k, v in d.items() if k != "rehearse"},
              **d["rehearse"])


@pytest.fixture(scope="module")
def toy():
  return _rehearsed(needs.CONFIG)


@pytest.fixture(scope="module")
def fam():
  return loader.load_module("families", "mimo_v2_flash")


def test_the_tests_copy_of_the_family_is_this_file():
  with open(FAMILY) as a, \
      open(os.path.join(ROOT, "tests", "mimo_v2_flash_family.py")) as b:
    assert a.read() == b.read()


def test_the_reference_imports_nothing_of_the_program():
  """Only the function of the program half that builds its config names the
  package; the reference writes the window as a mask and the sink as a
  softmax column that is dropped."""
  with open(FAMILY) as f:
    text = f.read()
  lines = [ln for ln in text.splitlines()
           if "import" in ln and "tensorflowonspark_tpu" in ln]
  assert lines == ["  from tensorflowonspark_tpu.models import transformer "
                   "as tfm"]
  reference = text.split("# the plain reference")[1].split(
      "# the program half")[0]
  assert "jnp.concatenate([scores, col], axis=-1)" in reference
  assert "[..., :-1]" in reference and "keep = t[None, :] <= at[:, None]" \
      in reference
  for word in ("logsumexp", "lse)", "cached", "ring_"):  # no trick, no ring
    assert word not in reference, word


def test_configuration_is_the_catalogs_but_for_the_three_reduced_keys():
  """Every width of the catalog's ``config`` unchanged; ``reduced`` = depth,
  experts held, vocabulary, with the published counts and the 16-chip
  deployment beside them; each assumption listed, the multi-token-prediction
  layers named as left out."""
  c = loader.load_json(needs.CONFIG)
  published = dict(
      hidden_size=4096, num_attention_heads=64, swa_num_attention_heads=64,
      head_dim=192, swa_head_dim=192, v_head_dim=128, swa_v_head_dim=128,
      num_key_value_heads=4, swa_num_key_value_heads=8, sliding_window=128,
      add_swa_attention_sink_bias=True, add_full_attention_sink_bias=False,
      partial_rotary_factor=0.334, rope_theta=5000000, swa_rope_theta=10000,
      attention_value_scale=0.707, attention_bias=False,
      intermediate_size=16384, moe_intermediate_size=2048,
      num_experts_per_tok=8, n_shared_experts=None, scoring_func="sigmoid",
      topk_method="noaux_tc", n_group=1, topk_group=1, norm_topk_prob=True,
      routed_scaling_factor=None, layernorm_epsilon=1e-05, hidden_act="silu",
      tie_word_embeddings=False, max_position_embeddings=262144,
      model_type="mimo_v2_flash")
  assert {k: c[k] for k in published} == published
  assert c["hybrid_layer_pattern"] == [0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 7 \
      + [0]
  assert c["moe_layer_freq"] == [0] + [1] * 47
  assert int(192 * c["partial_rotary_factor"]) == 64
  catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
  if os.path.exists(catalog):
    with open(catalog) as f:
      row = [r for r in map(json.loads, f) if r["name"] == "MiMo-V2-Flash"][0]
    assert {k: c[k] for k in row["config"] if k not in REDUCED} \
        == {k: v for k, v in row["config"].items() if k not in REDUCED}
    assert {k: c[k + "_published"] for k in REDUCED} \
        == {k: row["config"][k] for k in REDUCED}
    assert c["source"] == row["source_url"]
  assert {k: c[k] for k in REDUCED} == dict(
      num_hidden_layers=7, n_routed_experts=16, vocab_size=19072)
  assert c["vocab_size"] * 8 == c["vocab_size_published"]
  assert c["n_routed_experts"] * 16 == c["n_routed_experts_published"]
  assert (c["layers_kept"], c["experts_first"]) == ("published layers 0-6", 0)
  entry = [e for e in loader.load_json(os.path.join(ROOT, "BENCHMARK.json"))[
      "configs"] if e["name"] == "mimo-v2-flash"][0]
  assert entry["reduced"] == c["reduced"] == REDUCED
  assert entry["source"] == c["source"]
  assert "16-chip deployment" in c["deployment"]
  assumed = " ".join(c["assumed"])
  for word in ("sits on the VALUES", "FIRST int(", "half-split",
               "i - 128 < j <= i", "ONE learned scalar a QUERY head",
               "selection only", "NO shared expert", "no bias",
               "multi-token-prediction", "LEFT OUT", "RING",
               "N(0, 1/fan_in)", "sinks N(4, 1)"):
    assert word in assumed, word
  assert c["compute_dtype"] == "bfloat16" and "float32_activations" not in c


def test_program_tree_is_the_programs_own(fam, toy):
  import jax
  import jax.numpy as jnp
  from flax.core import meta
  from tensorflowonspark_tpu.models import transformer as tfm
  cfg = fam.program_config(toy, 96)
  assert cfg.layer_windows == (0, 8, 8, 8, 8, 0, 8)
  assert cfg.layer_kv_heads == (1, 2, 2, 2, 2, 1, 2)
  assert cfg.layer_sink == (False, True, True, True, True, False, True)
  assert cfg.ffn_types == ("mlp",) + ("experts",) * 6
  assert (cfg.head_dim, cfg.v_head_dim, cfg.rope_dim, cfg.attn_value_scale,
          cfg.experts_shared) == (24, 16, 8, 0.707, 0)
  want = meta.unbox(jax.eval_shape(lambda: tfm.Transformer(cfg).init(
      jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
  got = fam.program_params(1, toy)
  assert jax.tree.structure(want) == jax.tree.structure(got)
  assert all(a.shape == b.shape for a, b in
             zip(jax.tree.leaves(want), jax.tree.leaves(got)))
  assert fam.param_count(toy) == sum(x.size for x in jax.tree.leaves(got))
  full = loader.load_json(needs.CONFIG)
  assert fam.param_count(full) == full["parameters_as_built"] == 3429955392


def test_the_gates_configuration_is_the_cells(fam):
  """``tools/mosaic_gate.mimo_cfg`` spells the configuration out by hand; it
  is what the family builds from the configuration and traffic files."""
  from tools import mosaic_gate
  full = {k: v for k, v in loader.load_json(needs.CONFIG).items()
          if k != "rehearse"}
  traffic = loader.load_json(TRAFFIC)
  assert (traffic["slots"], traffic["max_seq"]) == (
      mosaic_gate.MIMO_SLOTS, mosaic_gate.MIMO_MAX_SEQ)
  assert fam.program_config(full, traffic["max_seq"]) \
      == mosaic_gate.mimo_cfg()


def test_forward_matches_reference_in_f32(fam, toy):
  import numpy as np
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import transformer as tfm
  toks = np.random.default_rng(3).integers(0, toy["vocab_size"], (2, 48),
                                           dtype=np.int32)
  cfg = fam.program_config(toy, 96, dtype=jnp.float32)
  out = tfm.Transformer(cfg).apply({"params": fam.program_params(11, toy)},
                                   toks)
  weights = fam.make_weights(11, toy)
  ref = fam.reference_logits(weights, toks, toy)
  # same mathematics, both float32: summation order alone
  assert float(jnp.abs(out - ref).max()) < 2e-4
  # and the reference without its sinks is another model
  less = fam.reference_logits(weights, toks, toy, "no_sink")
  assert float(jnp.abs(less - ref).max()) > 0.1


def _spec(tmp_path, toy, control=False):
  return dict(cell="test", chips=1, config=toy, traffic=_rehearsed(TRAFFIC),
              seed=5, seconds=1.5, trace=False, rehearse=True,
              control=control, run_dir=str(tmp_path), t_start=0.0)


def test_rehearsal_is_correct_and_control_and_altered_token_are_not(
    tmp_path, monkeypatch, toy):
  """The serve runner's whole child in this process at the cell's rehearsal
  sizes: sound, it is ``correct``, counts window rows beside context rows and
  ring reads beside all reads, and writes every leaf through the slab in
  place; the fp8 control's first tokens lie beyond the limit; with the served
  tokens altered where they are produced, ``correct`` comes out false."""
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
  assert 0 < d["window_context_tokens"] < d["live_context_tokens"]
  assert d["window_context_tokens"] <= 8 * d["live_slot_steps"]
  assert d["moe_assignments_held"] > 0 and d["moe_experts_touched"] > 0
  assert d["slab_in_place"] == d["slab_dispatches"] > 0
  # 7 layers x (K, V) leaves and 7 reads, 5 of them over a ring, horizon 4,
  # every dispatch (the window may open or close between two counters)
  assert d["cursor_leaf_writes"] % (14 * 4) == 0
  assert abs(d["cursor_leaf_writes"] // (14 * 4)
             - d["decode_dispatches"]) <= 1
  assert d["decode_attn_reads"] * 2 == d["cursor_leaf_writes"]
  assert d["decode_attn_reads_ring"] * 7 == d["decode_attn_reads"] * 5
  assert abs(d["prefill_chunks"] - d["prefills"]) <= 1    # the padded plan
  assert 0 < _read("window_rows_saved_share.mimo", rep) < 100
  assert 0 < _read("moe_held_assignments_per_token.mimo", rep) < 2
  assert 0 < _read("moe_experts_touched_share.mimo", rep) <= 100
  assert _read("prefill_tok_s.mimo", rep) > 0
  # a step's time on the CPU is no device number: nothing is read from it,
  # and an untraced run has no kernel time
  assert _read("decode_step_needed_gb_s.mimo", rep) is None
  assert _read("decode_attention_roofline.mimo", rep) is None
  assert _read("decode_step_needed_gb_s.mimo", dict(
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


def test_the_dropped_sink_control_fails_the_rehearsals_limits(monkeypatch):
  """``benchmarks/tools/sink_control.py`` at the rehearsal's sizes (its whole
  ``main``, as the builder calls it on the chip): the sound run is
  ``correct``, the reference WITHOUT its sinks in the program's place is not,
  and neither is the fp8 one."""
  sys.path.insert(0, os.path.join(ROOT, "benchmarks", "tools"))
  try:
    import sink_control
  finally:
    sys.path.pop(0)
  base = loader.load_module("runners", "serve_engine")
  # the tool replaces the runner's reference pass: put it back afterwards
  monkeypatch.setattr(base, "_reference_gaps", base._reference_gaps)
  assert sink_control.main(["--workload", CELL, "--seed", "5", "--seconds",
                            "1.5", "--rehearse"]) == 0
  out = loader.load_json(os.path.join(
      ROOT, "chiprun_out", "sink_control-%s-5.json" % CELL))
  assert out["sound"]["correct"] and not out["sound"]["failed"]
  for control in ("no_sink", "fp8"):
    assert not out[control]["correct"], out[control]
    assert "served_logit_gap_mean" in out[control]["failed"]
  assert out["no_sink"]["gap_mean"] > 10 * out["limits"][
      "served_logit_gap_mean_max"]



# -- what a step needs, and the six readers -----------------------------------


def test_needs_against_a_hand_count_at_the_published_sizes():
  """Attention: a full layer 4096 x (12288 + 768 + 512) + 8192 x 4096 =
  89,128,960 bf16 numbers, a window layer 4096 x (12288 + 1536 + 1024) + 8192
  x 4096 = 94,371,840 and 64 float32 sinks; an expert 3 x 4096 x 2048 =
  25,165,824 = 50.3 MB; the router 4096 x 256 + 256 float32; the dense MLP 3 x
  4096 x 16384; embedding and head 19072 x 4096 each. As built 3,429,955,392
  parameters = 6.87 GB; 1.88 GB every token passes; a token 2560 B a full
  layer and 5120 B a window layer; the slab 2.79 GB at 32 x 16384 where seven
  whole-context pairs would be 16.1."""
  z = needs.sizes()
  full = 4096 * (12288 + 768 + 512) + 8192 * 4096
  window = 4096 * (12288 + 1536 + 1024) + 8192 * 4096
  expert = 3 * 4096 * 2048
  assert (full, window, expert) == (89128960, 94371840, 25165824)
  assert z["expert_params"] == expert and expert * 2 == 50331648
  assert (z["layers"], z["window_layers"], z["full_layers"],
          z["expert_layers"], z["held"], z["window"]) == (7, 5, 2, 6, 16, 128)
  assert z["full_token_bytes"] == (4 * 192 + 4 * 128) * 2 == 2560
  assert z["window_token_bytes"] == (8 * 192 + 8 * 128) * 2 == 5120
  dense = 2 * full + 5 * window + 3 * 4096 * 16384 + 4096 * 19072
  f32 = 7 * 2 * 4096 + 4096 + 6 * (4096 + 1) * 256 + 5 * 64
  assert (z["dense_params"], z["f32_params"]) == (dense, f32)
  params = dense + f32 + 19072 * 4096 + 6 * 16 * expert
  assert params == needs.param_count() == 3429955392 \
      == loader.load_json(needs.CONFIG)["parameters_as_built"]
  assert needs.weight_bytes() == (params - f32) * 2 + f32 * 4
  assert 6.86e9 < needs.weight_bytes() < 6.88e9
  assert needs.passed_bytes() == dense * 2 + f32 * 4
  assert 1.87e9 < needs.passed_bytes() < 1.89e9
  assert 4.82e9 < 96 * expert * 2 < 4.84e9
  assert needs.slab_bytes(32, 16384) == 32 * (2 * 2560 * 16384
                                              + 5 * 5120 * 128)
  assert 2.78e9 < needs.slab_bytes(32, 16384) < 2.80e9
  assert 16.0e9 < 32 * 16384 * (2 * 2560 + 5 * 5120) < 16.2e9
  # 32 live lanes at 3500 positions each, 38 experts touched: the weights
  # every token passes, 38 experts, the context in two full layers and the
  # window's 128 rows of it in five, 32 rows written in seven
  got = needs.decode_step_bytes(32, 38, 32 * 3500, 32 * 128)
  assert got == dense * 2 + f32 * 4 + 38 * expert * 2 \
      + 32 * 3500 * 2 * 2560 + 32 * 128 * 5 * 5120 \
      + 32 * (2 * 2560 + 5 * 5120)
  assert 4.4e9 < got < 4.6e9
  # a call of the decode kernel: the live rows of ONE leaf pair
  assert needs.decode_attention_bytes(32 * 3500, False) == 32 * 3500 * 2560
  assert needs.decode_attention_bytes(32 * 128, True) == 32 * 128 * 5120
  # the sizes are the configuration file's own: a window of 64 halves what
  # the slab's rings hold
  half = dict(loader.load_json(needs.CONFIG), sliding_window=64)
  assert needs.slab_bytes(32, 16384, half) \
      == 32 * (2 * 2560 * 16384 + 5 * 5120 * 64)


def _report(**delta):
  d = dict(steps=1000, live_slot_steps=31000, live_context_tokens=110_000_000,
           window_context_tokens=3_968_000, moe_assignments_held=93000,
           moe_experts_touched=38000, t_decode_dispatch_s=2.0,
           t_decode_fetch_s=8.0, decode_attn_reads=7000,
           decode_attn_reads_ring=5000)
  d.update(delta)
  return dict(stats_delta=d, device=dict(platform="tpu", kind="TPU v5 lite"),
              trace_summary=dict(kernels={
                  "%decode_attention": dict(seconds=0.160, calls=420.0),
                  "%cursor_write": dict(seconds=0.01, calls=840.0)}),
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
  assert _read("moe_held_assignments_per_token.mimo", rep) \
      == pytest.approx(93000 / (31000 * 6)) == 0.5
  assert _read("moe_experts_touched_share.mimo", rep) \
      == pytest.approx(100 * 38000 / (1000 * 6 * 16))
  assert _read("window_rows_saved_share.mimo", rep) \
      == pytest.approx(100 * (1 - 3.968 / 110))
  assert _read("prefill_tok_s.mimo", rep) == pytest.approx(4608 / 0.5)
  nbytes = needs.decode_step_bytes(31, 38, 110000, 3968)
  # 10 ms a step on the loop thread's clock: GB a second, no peak in it
  assert _read("decode_step_needed_gb_s.mimo", rep) \
      == pytest.approx(nbytes / 1e9 / 0.010)
  # 420 calls traced, 5 of 7 over a ring of 3968 live rows at 5120 B, 2 of 7
  # over 110,000 live rows at 2560 B: the least time at 819 GB/s over 0.160 s
  a_call = 5 / 7 * 3968 * 5120 + 2 / 7 * 110000 * 2560
  assert _read("decode_attention_roofline.mimo", rep) \
      == pytest.approx(100 * 420 * a_call / 819e9 / 0.160)


def test_the_kernels_roofline_stays_under_100_when_calls_read_whole_blocks():
  """A fixture whose calls ran AT the HBM peak over the bytes they really
  moved: every slot's live rows rounded UP to whole blocks of 128 (a lane at
  3437 rows reads 27 blocks = 3456 rows; a ring's 128 rows are one block).
  The reader counts live rows only, so it reads under 100."""
  lanes, cursor, steps = 32, 3437, 1000
  blocks = -(-cursor // 128) * 128
  moved_full = lanes * blocks * 2560              # a full-layer call
  moved_ring = lanes * 128 * 5120                 # a ring call
  calls = 7 * 60
  seconds = (2 * 60 * moved_full + 5 * 60 * moved_ring) / 819e9
  rep = _report(
      steps=steps, live_slot_steps=lanes * steps,
      live_context_tokens=lanes * cursor * steps,
      window_context_tokens=lanes * 128 * steps)
  rep["trace_summary"]["kernels"]["%decode_attention"] = dict(
      seconds=seconds, calls=float(calls))
  got = _read("decode_attention_roofline.mimo", rep)
  assert 95.0 < got < 100.0, got
  # a device that is not in the table is an error, not a default
  with pytest.raises(ValueError, match="unknown device_kind"):
    _read("decode_attention_roofline.mimo",
          dict(rep, device=dict(platform="tpu", kind="TPU v9")))


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_from_a_program_without_the_counters(name):
  """The parent of PR 38 has no ``decode_attn_reads_ring``, a Trinity-era
  program none of this cell's counters, an idle window no step, an untraced
  run no kernel: the reader returns nothing and does not raise."""
  assert _read(name, {}) is None
  assert _read(name, dict(stats_delta=dict(
      steps=8, live_slot_steps=20, live_context_tokens=100,
      window_context_tokens=50, moe_assignments_held=5, moe_experts_touched=4,
      decode_attn_reads=56, t_decode_dispatch_s=0.1, t_decode_fetch_s=0.1),
                              requests=[])) is None
  idle = _report(steps=0, live_slot_steps=0)
  idle["requests"] = []
  assert _read(name, idle) is None
  if name == "decode_attention_roofline.mimo":
    assert _read(name, dict(_report(), trace_summary=None)) is None
    assert _read(name, dict(_report(), trace_summary=dict(kernels={}))) is None


def test_the_new_entries_keep_the_contract():
  """Looked up BY NAME: a later PR appends after them."""
  b = loader.load_json(os.path.join(ROOT, "BENCHMARK.json"))
  cell = [w for w in b["workloads"] if w["name"] == CELL]
  assert len(cell) == 1 and cell[0]["chips"] == 1
  assert (cell[0]["config"], cell[0]["traffic"]) == (
      "mimo-v2-flash", "serve-backlog-16k-two-regimes")
  assert len(cell[0]["why"]) <= 200
  by_name = {m["name"]: m for m in b["per_layer"]}
  for name in READERS:
    m = by_name[name]
    assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
    assert m["layer"] == ("kernels" if "roofline" in name
                          else "model step, serving")
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
  roof = by_name["decode_attention_roofline.mimo"]
  assert (roof["unit"], roof["source"]) == ("%", "device_trace")
  serve = [m for m in b["end_to_end"] if m["name"] == "serve_tok_s"][0]
  assert CELL in serve["workloads"]
  for name in (
      "compile_s", "cache_hits", "slot_occupancy", "decode_step_ms",
      "device_idle_share.backlog", "decode_step_inner_ms.backlog",
      "loop_host_share.backlog", "slab_in_place_share.backlog",
      "cursor_write_dma_share.backlog", "decode_attn_ragged_share.backlog",
      "prefill_chunks_per_prompt.backlog", "prefill_pad_share.backlog",
      "device_empty_share.backlog", "empty_in_prefill_share.backlog",
      "empty_in_decode_share.backlog", "prefill_dispatch_ms.backlog",
      "decode_dispatch_ms.backlog"):
    assert CELL in by_name[name]["workloads"], name
  assert len(b["workloads"]) == 7 and all(w["chips"] == 1
                                          for w in b["workloads"])


def test_the_traffic_file_is_the_issues():
  t = loader.load_json(TRAFFIC)
  assert (t["runner"], t["loop"], t["max_seq"]) \
      == (TRAFFIC_RUNNER, "closed", 16384)
  assert t["slots"] in (24, 32, 48) and t["clients"] == (
      48 if t["slots"] == 24 else 2 * t["slots"])
  mix = t["mix"]
  assert mix["prompt_lens"] == [256, 512, 1024, 2048, 4096, 8192, 12288]
  assert mix["prompt_weights"] == [5, 6, 6, 4, 4, 5, 2]
  assert mix["output_lens"] == [128, 256, 512, 768, 1024]
  assert mix["output_weights"] == [6, 8, 8, 6, 4]
  assert (mix["pool"], mix["mix_seed"], mix["max_total"]) == (32, 3801, 16384)
  assert (t["ramp_seconds"], t["drain_seconds"], t["check_requests"],
          t["trace_seconds"]) == (20, 0, 8, 3.0)
  from benchmarks.lib import traffic
  pool = traffic.size_pool(mix)
  assert len(pool) == 32 and all(p + o <= 16384 for p, o in pool)
  # every prompt passes the window of 128; 7 of 32 are 8192 tokens or more
  assert min(p for p, _ in pool) > 128
  assert sum(p >= 8192 for p, _ in pool) == 7
  assert sum(p for p, _ in pool) / 32 == pytest.approx(3144, abs=1)
  assert sum(o for _, o in pool) / 32 == pytest.approx(488, abs=1)
  assert t["rehearse"]["slots"] == 4 and t["rehearse"]["max_seq"] == 96
  # the rehearsal's prompts pass its window of 8 and its ring of 16 rows
  assert max(t["rehearse"]["mix"]["prompt_lens"]) > 16
  assert set(t["limits"]) == {"checked_tokens_min", "served_logit_gap_max",
                              "served_logit_gap_mean_max"}
