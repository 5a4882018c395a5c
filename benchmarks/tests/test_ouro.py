"""The ``ouro`` family, its configuration file, its cell and its three readers
(CPU only: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``).
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
from benchmarks.lib import needs_ouro as needs  # noqa: E402

CELL = "ouro-serve-backlog"
READERS = ("decode_step_needed_gb_s.ouro", "mean_exit_pass.ouro",
           "prefill_tok_s.ouro")
TRAFFIC = os.path.join(ROOT, "benchmarks", "traffic", "serve-backlog-512.json")


def _rehearsed(path):
  d = loader.load_json(path)
  return dict({k: v for k, v in d.items() if k != "rehearse"},
              **d["rehearse"])


@pytest.fixture(scope="module")
def toy():
  return _rehearsed(needs.CONFIG)


@pytest.fixture(scope="module")
def fam():
  return loader.load_module("families", "ouro")


def test_the_tests_copy_of_the_family_is_this_file():
  with open(os.path.join(ROOT, "benchmarks", "families", "ouro.py")) as a, \
      open(os.path.join(ROOT, "tests", "ouro_family.py")) as b:
    assert a.read() == b.read()


def test_the_reference_imports_nothing_of_the_program():
  """Only the two functions of the program half name the package."""
  with open(os.path.join(ROOT, "benchmarks", "families", "ouro.py")) as f:
    lines = [ln for ln in f.read().splitlines()
             if "import" in ln and "tensorflowonspark_tpu" in ln]
  assert lines == ["  from tensorflowonspark_tpu.models import transformer "
                   "as tfm"]


def test_configuration_is_the_catalogs_with_nothing_reduced():
  """Every key of the catalog's ``config`` unchanged (where the catalog is
  installed), ``reduced`` empty, each assumption listed."""
  c = loader.load_json(needs.CONFIG)
  published = dict(
      hidden_size=2048, num_hidden_layers=48, num_attention_heads=16,
      num_key_value_heads=16, head_dim=128, intermediate_size=5632,
      hidden_act="silu", rms_norm_eps=1e-06, rope_theta=1000000,
      rope_scaling=None, sliding_window=None, tie_word_embeddings=False,
      vocab_size=49152, total_ut_steps=4, early_exit_threshold=1,
      max_position_embeddings=65536, model_type="ouro")
  assert {k: c[k] for k in published} == published
  assert c["layer_types"] == ["full_attention"] * 48
  catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
  if os.path.exists(catalog):
    with open(catalog) as f:
      row = [r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B"][0]
    assert {k: c[k] for k in row["config"]} == row["config"]
    assert c["source"] == row["source_url"]
  entry = [e for e in loader.load_json(os.path.join(ROOT, "BENCHMARK.json"))[
      "configs"] if e["name"] == "ouro-2.6b"][0]
  assert entry["reduced"] == c["reduced"] == []
  assert entry["source"] == c["source"]
  assert "nothing is shared or left out" in c["deployment"]
  assumed = " ".join(c["assumed"])
  for word in ("OWN keys and values", "no bias", "four RMSNorms",
               "INSIDE the loop", "half-split", "exit gate"):
    assert word in assumed, word
  assert c["compute_dtype"] == "bfloat16" and "float32_activations" not in c


def test_program_tree_is_the_programs_own(fam, toy):
  import jax
  import jax.numpy as jnp
  from flax.core import meta
  from tensorflowonspark_tpu.models import transformer as tfm
  cfg = fam.program_config(toy, 64)
  assert (cfg.loop_passes, cfg.post_norm, cfg.rope_theta) == (4, True, 1e6)
  want = meta.unbox(jax.eval_shape(lambda: tfm.Transformer(cfg).init(
      jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
  got = fam.program_params(1, toy)
  assert jax.tree.structure(want) == jax.tree.structure(got)
  assert all(a.shape == b.shape for a, b in
             zip(jax.tree.leaves(want), jax.tree.leaves(got)))
  assert fam.param_count(toy) == sum(x.size for x in jax.tree.leaves(got))
  full = loader.load_json(needs.CONFIG)
  assert fam.param_count(full) == full["parameters_as_built"] == 2667974657


def test_the_gates_configuration_is_the_cells(fam):
  """``tools/mosaic_gate.ouro_cfg`` spells the configuration out by hand; it
  is what the family builds from the configuration and traffic files."""
  from tools import mosaic_gate
  full = {k: v for k, v in loader.load_json(needs.CONFIG).items()
          if k != "rehearse"}
  traffic = loader.load_json(TRAFFIC)
  assert (traffic["slots"], traffic["max_seq"]) == (
      mosaic_gate.OURO_SLOTS, mosaic_gate.OURO_MAX_SEQ)
  assert fam.program_config(full, traffic["max_seq"]) \
      == mosaic_gate.ouro_cfg()


def test_forward_matches_reference_in_f32(fam, toy):
  import numpy as np
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import transformer as tfm
  toks = np.random.default_rng(3).integers(0, toy["vocab_size"], (2, 48),
                                           dtype=np.int32)
  cfg = fam.program_config(toy, 64, dtype=jnp.float32)
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
  sizes: sound, it is ``correct``, counts 4 passes a token and writes every
  leaf through the slab in place; the fp8 control's first tokens lie beyond
  the limit; with the served tokens altered where they are produced,
  ``correct`` comes out false."""
  from tensorflowonspark_tpu.serving import slots as slots_lib
  runner = loader.load_module("runners", "serve_engine")
  spec = _spec(tmp_path, toy, control=True)
  limits = spec["traffic"]["limits"]
  path = os.path.join(str(tmp_path), "sound.json")
  runner.child_main(spec, path)
  rep = loader.load_json(path)
  assert all(c["ok"] for c in runner.checks_from(rep, limits))
  assert rep["checked_tokens"] >= 10
  assert rep["control_gap_max"] > limits["served_logit_gap_max"]
  d = rep["stats_delta"]
  assert d["loop_exit_pass_sum"] == 4 * d["live_slot_steps"] > 0
  assert d["live_context_tokens"] > 0
  assert d["slab_in_place"] == d["slab_dispatches"] > 0
  # 3 layers x 4 passes x (K, V) leaves, horizon 4, every dispatch (the
  # window may open or close between the two counters of one dispatch)
  assert d["cursor_leaf_writes"] % (24 * 4) == 0
  assert abs(d["cursor_leaf_writes"] // (24 * 4)
             - d["decode_dispatches"]) <= 1
  assert abs(d["prefill_chunks"] - d["prefills"]) <= 1    # the padded plan
  assert _read("mean_exit_pass.ouro", rep) == 4.0
  assert _read("prefill_tok_s.ouro", rep) > 0
  # a step's time on the CPU is no device number: nothing is read from it
  assert _read("decode_step_needed_gb_s.ouro", rep) is None
  assert _read("decode_step_needed_gb_s.ouro", dict(
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


# -- what a step needs, and the three readers ---------------------------------


def test_needs_against_a_hand_count_at_the_published_sizes():
  """A layer: 4 x 2048^2 + 3 x 2048 x 5632 bf16 matrix numbers and four
  float32 norm scales; 48 of them 4 times a step, the final norm and the
  gate 4 times, the 2048 x 49152 head once: 19.9 GB. A token: 4 passes x 48
  layers x (K + V) x 2048 bf16 numbers = 1.5 MiB."""
  layer = (4 * 2048 * 2048 + 3 * 2048 * 5632) * 2 + 4 * 2048 * 4
  weights = 4 * (48 * layer + (2 * 2048 + 1) * 4) + 2048 * 49152 * 2
  assert needs.weight_bytes() == weights
  assert 19.9e9 < weights < 19.95e9
  assert needs.token_cache_bytes() == 4 * 48 * 2 * 2048 * 2 \
      == 1.5 * 1024 * 1024
  # 8 live lanes holding 2000 tokens between them: weights, the context
  # read, and 8 rows written
  assert needs.decode_step_bytes(8, 2000) \
      == weights + (2000 + 8) * 1.5 * 1024 * 1024
  # the sizes are the configuration file's own: half the passes, half the
  # layer weights and half a token's cache
  two = dict(loader.load_json(needs.CONFIG), total_ut_steps=2)
  assert needs.token_cache_bytes(two) * 2 == needs.token_cache_bytes()
  assert needs.weight_bytes(two) == 2 * (48 * layer + (2 * 2048 + 1) * 4) \
      + 2048 * 49152 * 2


def _report(**delta):
  d = dict(steps=1000, live_slot_steps=7600, live_context_tokens=1_500_000,
           loop_exit_pass_sum=30400, t_decode_dispatch_s=3.0,
           t_decode_fetch_s=37.0)
  d.update(delta)
  return dict(stats_delta=d, device=dict(platform="tpu", kind="TPU v5 lite"),
              requests=[dict(prompt_len=256, started_at=10.0,
                             prefill_done_at=10.4),
                        dict(prompt_len=64, started_at=11.0,
                             prefill_done_at=11.1),
                        dict(prompt_len=50, started_at=None,
                             prefill_done_at=None)])


def _read(name, report):
  return loader.load_module("layer_metrics", name).read(report)


def test_readers_arithmetic():
  rep = _report()
  assert _read("mean_exit_pass.ouro", rep) == 4.0
  assert _read("mean_exit_pass.ouro", _report(loop_exit_pass_sum=22800)) \
      == 3.0
  assert _read("prefill_tok_s.ouro", rep) == pytest.approx(320 / 0.5)
  nbytes = needs.decode_step_bytes(7.6, 1500)
  # 40 ms a step on the loop thread's clock: GB a second, no peak in it
  assert _read("decode_step_needed_gb_s.ouro", rep) \
      == pytest.approx(nbytes / 1e9 / 0.040)
  assert 22e9 < nbytes < 23e9


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_from_a_program_without_the_counters(name):
  """The parent of PR 30 has neither the loop nor its counter, and an idle
  window no step: the reader returns nothing and does not raise."""
  assert _read(name, {}) is None
  assert _read(name, dict(stats_delta=dict(
      steps=8, live_slot_steps=20, live_context_tokens=100,
      t_decode_dispatch_s=0.1, t_decode_fetch_s=0.1), requests=[])) is None
  idle = _report(steps=0, live_slot_steps=0)
  idle["requests"] = []
  assert _read(name, idle) is None


def test_the_new_entries_keep_the_contract():
  """Looked up BY NAME: a later PR appends after them."""
  b = loader.load_json(os.path.join(ROOT, "BENCHMARK.json"))
  cell = [w for w in b["workloads"] if w["name"] == CELL]
  assert len(cell) == 1 and cell[0]["chips"] == 1
  assert (cell[0]["config"], cell[0]["traffic"]) == ("ouro-2.6b",
                                                     "serve-backlog-512")
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
  reported = sorted(m["name"] for m in b["per_layer"]
                    if CELL in m["workloads"])
  assert reported == sorted(READERS + (
      "compile_s", "cache_hits", "slot_occupancy", "decode_step_ms",
      "device_idle_share.backlog", "decode_step_inner_ms.backlog",
      "loop_host_share.backlog", "slab_in_place_share.backlog",
      "cursor_write_dma_share.backlog", "prefill_chunks_per_prompt.backlog",
      "prefill_pad_share.backlog"))
  assert len(b["workloads"]) <= 24 and all(w["chips"] == 1
                                           for w in b["workloads"])


def test_the_traffic_file_is_the_issues():
  t = loader.load_json(TRAFFIC)
  assert (t["runner"], t["loop"], t["slots"], t["clients"], t["max_seq"]) \
      == ("serve_engine", "closed", 8, 16, 512)
  mix = t["mix"]
  assert mix["prompt_lens"] == [24, 32, 48, 64, 96, 128, 192, 256]
  assert mix["prompt_weights"] == [2, 3, 4, 5, 4, 3, 2, 1]
  assert mix["output_lens"] == [64, 96, 128, 192, 256]
  assert mix["output_weights"] == [4, 6, 6, 5, 3]
  assert (mix["pool"], mix["mix_seed"], mix["max_total"]) == (24, 2510, 512)
  assert (t["ramp_seconds"], t["drain_seconds"], t["check_requests"],
          t["trace_seconds"]) == (15, 0, 8, 3.0)
  from benchmarks.lib import traffic
  pool = traffic.size_pool(mix)
  assert len(pool) == 24 and all(p + o <= 512 for p, o in pool)
  assert t["rehearse"]["slots"] == 4 and t["rehearse"]["max_seq"] == 96
