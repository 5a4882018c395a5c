"""The ``kimi_linear`` family, its configuration file, its cell and its four
readers (CPU only: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``).
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)

from benchmarks.lib import loader  # noqa: E402
from benchmarks.lib import needs_kimi_linear as needs  # noqa: E402

CELL = "kimi-linear-serve-backlog"


def _rehearsed(path):
  d = loader.load_json(path)
  return dict({k: v for k, v in d.items() if k != "rehearse"},
              **d["rehearse"])


@pytest.fixture(scope="module")
def toy():
  return _rehearsed(needs.CONFIG)


@pytest.fixture(scope="module")
def fam():
  return loader.load_module("families", "kimi_linear")


def test_the_tests_copy_of_the_family_is_this_file():
  with open(os.path.join(ROOT, "benchmarks", "families",
                         "kimi_linear.py")) as a, \
      open(os.path.join(ROOT, "tests", "kimi_linear_family.py")) as b:
    assert a.read() == b.read()


def test_configuration_keeps_the_published_sizes():
  """Every width, the router's 256 outputs, 8 a token, the 3:1 pattern and
  all 27 layers as published; only the experts held and the vocabulary are
  the chip's share, and the file says so."""
  c = loader.load_json(needs.CONFIG)
  published = dict(
      hidden_size=2304, intermediate_size=9216, num_hidden_layers=27,
      num_attention_heads=32, num_key_value_heads=32, head_dim=72,
      kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
      v_head_dim=128, moe_intermediate_size=1024, num_experts_per_token=8,
      num_shared_experts=1, first_k_dense_replace=1,
      routed_scaling_factor=2.446, rms_norm_eps=1e-05, q_lora_rank=None,
      mla_use_nope=True, tie_word_embeddings=False, num_expert_group=1,
      topk_group=1, moe_router_activation_func="sigmoid",
      num_experts_published=256, vocab_size_published=163840)
  assert {k: c[k] for k in published} == published
  lin = c["linear_attn_config"]
  assert lin["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
  assert sorted(lin["kda_layers"] + lin["full_attn_layers"]) \
      == list(range(1, 28))
  assert (lin["head_dim"], lin["num_heads"],
          lin["short_conv_kernel_size"]) == (128, 32, 4)
  entry = [e for e in loader.load_json(os.path.join(ROOT, "BENCHMARK.json"))[
      "configs"] if e["name"] == "kimi-linear-48b-a3b"][0]
  assert sorted(entry["reduced"]) == ["num_experts", "vocab_size"] \
      == sorted(c["reduced_why"])
  assert c["num_experts"] == 16 and c["vocab_size"] * 8 == 163840
  assert "16 chips" in c["deployment"] and len(c["assumed"]) >= 4


def test_program_tree_is_the_programs_own(fam, toy):
  import jax
  import jax.numpy as jnp
  from flax.core import meta
  from tensorflowonspark_tpu.models import transformer as tfm
  cfg = fam.program_config(toy, 64)
  want = meta.unbox(jax.eval_shape(lambda: tfm.Transformer(cfg).init(
      jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
  got = fam.program_params(1, toy)
  assert jax.tree.structure(want) == jax.tree.structure(got)
  assert all(a.shape == b.shape for a, b in
             zip(jax.tree.leaves(want), jax.tree.leaves(got)))
  assert fam.param_count(toy) == sum(x.size for x in jax.tree.leaves(got))
  full = loader.load_json(needs.CONFIG)
  assert fam.param_count(full) == full["parameters_as_built"] == 4296057728


def test_the_gates_configuration_is_the_cells(fam):
  """``tools/mosaic_gate.kimi_linear_cfg`` spells the cut out by hand; it is
  what the family builds from the configuration and traffic files."""
  from tools import mosaic_gate
  full = {k: v for k, v in loader.load_json(needs.CONFIG).items()
          if k != "rehearse"}
  traffic = loader.load_json(os.path.join(
      ROOT, "benchmarks", "traffic", "serve-backlog-4k.json"))
  assert (traffic["slots"], traffic["max_seq"]) == (
      mosaic_gate.KIMI_LINEAR_SLOTS, mosaic_gate.KIMI_LINEAR_MAX_SEQ)
  assert fam.program_config(full, traffic["max_seq"]) \
      == mosaic_gate.kimi_linear_cfg()


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
  # same mathematics, both float32: summation order, and the chunkwise
  # form of the recurrence against the per-token one
  assert float(jnp.abs(out - ref).max()) < 5e-4


def _spec(tmp_path, toy, control=False):
  tr = _rehearsed(os.path.join(ROOT, "benchmarks", "traffic",
                               "serve-backlog-4k.json"))
  return dict(cell="test", chips=1, config=toy, traffic=tr, seed=5,
              seconds=1.5, trace=False, rehearse=True, control=control,
              run_dir=str(tmp_path), t_start=0.0)


def test_rehearsal_is_correct_and_control_and_altered_token_are_not(
    tmp_path, monkeypatch, toy):
  """The serve runner's whole child in this process at the cell's rehearsal
  sizes: sound, it is ``correct`` and counts its experts; the fp8 control's
  first tokens lie beyond the limit; with the served tokens altered where
  they are produced, ``correct`` comes out false."""
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
  assert d["moe_assignments_held"] > 0 and d["live_context_tokens"] > 0
  assert 0 < d["moe_experts_touched"] <= d["moe_assignments_held"]
  assert d["slab_in_place"] == d["slab_dispatches"] > 0

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


# -- the four readers ---------------------------------------------------------


def _report(**delta):
  d = dict(steps=1000, live_slot_steps=46000, moe_assignments_held=598000,
           moe_experts_touched=324000, live_context_tokens=60_000_000,
           t_decode_dispatch_s=2.0, t_decode_fetch_s=23.0)
  d.update(delta)
  return dict(stats_delta=d, device=dict(platform="tpu", kind="TPU v5 lite"),
              requests=[dict(prompt_len=1000, started_at=10.0,
                             prefill_done_at=10.5),
                        dict(prompt_len=200, started_at=11.0,
                             prefill_done_at=11.1),
                        dict(prompt_len=50, started_at=None,
                             prefill_done_at=None)])


def _read(name, report):
  return loader.load_module("layer_metrics", name).read(report)


def test_readers_arithmetic():
  rep = _report()
  assert _read("moe_held_assignments_per_token", rep) \
      == pytest.approx(598000 / (46000 * 26))
  assert _read("moe_experts_touched_share", rep) \
      == pytest.approx(100 * 324000 / (1000 * 26 * 16))
  assert _read("prefill_tok_s.kimi", rep) == pytest.approx(1200 / 0.6)
  z = needs.sizes()
  nbytes = needs.decode_step_bytes(46, 324, 60000)
  assert nbytes == pytest.approx(
      2 * 46 * 20 * (32 * 128 * 128 * 4 + 3 * 12288 * 4)
      + z["dense_params"] * 2 + z["f32_params"] * 4
      + 324 * 3 * 2304 * 1024 * 2 + 60000 * 7 * 576 * 2)
  assert 11e9 < nbytes < 13e9
  # 25 ms a step on the loop thread's clock: GB a second, no peak in it
  assert _read("decode_step_needed_gb_s.kimi", rep) \
      == pytest.approx(nbytes / 1e9 / 0.025)
  # the tail is counted in the dtype the configuration has it stored in
  bf16_tail = dict(loader.load_json(needs.CONFIG), float32_activations=False)
  assert nbytes - needs.decode_step_bytes(46, 324, 60000, bf16_tail) \
      == pytest.approx(2 * 46 * 20 * 3 * 12288 * 2)
  assert z["dense_params"] * 2 + z["f32_params"] * 4 \
      == pytest.approx(2.64e9, rel=0.01)


@pytest.mark.parametrize("name", [
    "moe_held_assignments_per_token", "moe_experts_touched_share",
    "decode_step_needed_gb_s.kimi", "prefill_tok_s.kimi"])
def test_readers_read_nothing_from_a_program_without_the_counters(name):
  """The parent of PR 26 has neither the layers nor the counters, and an
  idle window no step: the reader returns nothing and does not raise."""
  assert _read(name, {}) is None
  assert _read(name, dict(stats_delta=dict(steps=8, live_slot_steps=20),
                          requests=[])) is None
  idle = _report(steps=0, live_slot_steps=0)
  idle["requests"] = []
  assert _read(name, idle) is None


def test_the_new_entries_keep_the_contract():
  b = loader.load_json(os.path.join(ROOT, "BENCHMARK.json"))
  assert b["workloads"][-1]["name"] == CELL and b["workloads"][-1][
      "chips"] == 1
  assert [m["name"] for m in b["per_layer"][-4:]] == [
      "moe_held_assignments_per_token", "moe_experts_touched_share",
      "decode_step_needed_gb_s.kimi", "prefill_tok_s.kimi"]
  for m in b["per_layer"][-4:]:
    assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
  reported = [m["name"] for m in b["per_layer"] if CELL in m["workloads"]]
  assert "slab_in_place_share.backlog" in reported and len(reported) == 12
