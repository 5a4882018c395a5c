"""The ``keye_vl2`` family, its configuration file, its cell, its readers and
the tools of its mathematics' controls (CPU only:
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
from benchmarks.lib import needs_keye_vl2 as needs  # noqa: E402

CELL = "keye-vl2-serve-backlog"
COUNTER_READERS = ("decode_step_needed_gb_s.keye",
                   "moe_held_assignments_per_token.keye",
                   "moe_experts_touched_share.keye",
                   "sparse_rows_kept_share.keye",
                   "sparse_queries_limited_share.keye",
                   "index_rows_live_share.keye", "prefill_tok_s.keye")
TRACE_READERS = ("decode_attention_roofline.keye",
                 "expert_product_roofline.keye",
                 "index_select_device_share.keye")
READERS = COUNTER_READERS + TRACE_READERS
TRAFFIC = os.path.join(ROOT, "benchmarks", "traffic",
                       "serve-backlog-32k-sparse.json")
TRAFFIC_RUNNER = "serve_engine_vs_control"
FAMILY = os.path.join(ROOT, "benchmarks", "families", "keye_vl2.py")
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]


def _rehearsed(path):
  d = loader.load_json(path)
  return dict({k: v for k, v in d.items() if k != "rehearse"},
              **d["rehearse"])


@pytest.fixture(scope="module")
def toy():
  return _rehearsed(needs.CONFIG)


@pytest.fixture(scope="module")
def fam():
  return loader.load_module("families", "keye_vl2")


def test_the_tests_copy_of_the_family_is_this_file():
  with open(FAMILY) as a, \
      open(os.path.join(ROOT, "tests", "keye_vl2_family.py")) as b:
    assert a.read() == b.read()


def test_the_reference_imports_nothing_of_the_program():
  """Only the function of the program half that builds its config names the
  package; the reference writes the selection as a mask over the whole
  sequence from ``lax.top_k``'s threshold, caches nothing and searches no
  bits."""
  with open(FAMILY) as f:
    text = f.read()
  lines = [ln for ln in text.splitlines()
           if "import" in ln and "tensorflowonspark_tpu" in ln]
  assert lines == ["  from tensorflowonspark_tpu.models import transformer "
                   "as tfm"]
  reference = text.split("# the plain reference")[1].split(
      "# the program half")[0]
  assert "jax.lax.top_k(masked, k)" in reference
  assert "jnp.where(keep[:, None, None], scores, -1e30)" in reference
  assert "jax.nn.softmax(jnp.einsum(\"bsd,de->bse\", x," in reference
  for word in ("cached", "bitcast", "decode_attention", "flash", "approx"):
    assert word not in reference, word


def test_no_program_of_the_selection_is_approximate():
  """The selection is exact in every program: no ``approx_max_k`` /
  ``approx_min_k`` anywhere in the package or the family."""
  for base, _, files in os.walk(os.path.join(ROOT, "tensorflowonspark_tpu")):
    for name in files:
      if name.endswith(".py"):
        with open(os.path.join(base, name)) as f:
          assert "approx_m" not in f.read(), name
  with open(FAMILY) as f:
    assert "approx_m" not in f.read()


def test_configuration_is_the_catalogs_but_for_the_three_reduced_keys():
  """Every number of the catalog's ``config`` unchanged; ``reduced`` = depth,
  experts held, vocabulary, with the published counts and the 8-chip
  deployment beside them; each assumption listed, the tower and the
  three-part positions named as left out."""
  c = loader.load_json(needs.CONFIG)
  published = dict(
      hidden_size=2048, num_attention_heads=32, num_key_value_heads=4,
      head_dim=128, intermediate_size=6144, moe_intermediate_size=768,
      num_experts_per_tok=8, norm_topk_prob=True, decoder_sparse_step=1,
      mlp_only_layers=[], rope_theta=10000000, rms_norm_eps=1e-06,
      hidden_act="silu", attention_bias=False, tie_word_embeddings=False,
      max_position_embeddings=262144, max_window_layers=48,
      num_local_experts=128, sliding_window=None, use_sliding_window=False,
      model_type="KeyeVL2")
  assert {k: c[k] for k in published} == published
  assert c["sa_config"] == dict(
      indexer_head_dim=64, indexer_num_heads=16, indexer_num_kv_heads=1,
      kv_chunk_size=512, q_chunk_size=512, topk=2048)
  assert c["rope_scaling"]["mrope_section"] == [16, 24, 24]
  catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
  if os.path.exists(catalog):
    with open(catalog) as f:
      row = [r for r in map(json.loads, f)
             if r["name"] == "Keye-VL-2.0-30B-A3B"][0]
    assert {k: c[k] for k in row["config"] if k not in REDUCED} \
        == {k: v for k, v in row["config"].items() if k not in REDUCED}
    assert {k: c[k + "_published"] for k in REDUCED} \
        == {k: row["config"][k] for k in REDUCED}
    assert c["source"] == row["source_url"]
  assert {k: c[k] for k in REDUCED} == dict(
      num_hidden_layers=6, num_experts=16, vocab_size=19072)
  assert c["vocab_size"] % 128 == 0 \
      and c["vocab_size"] * 8 >= c["vocab_size_published"]
  assert c["num_experts"] * 8 == c["num_experts_published"]
  assert (c["layers_kept"], c["experts_first"]) == ("published layers 0-5", 0)
  entry = [e for e in loader.load_json(os.path.join(ROOT, "BENCHMARK.json"))[
      "configs"] if e["name"] == "keye-vl-2.0-30b-a3b"][0]
  assert entry["reduced"] == c["reduced"] == REDUCED
  assert entry["source"] == c["source"]
  assert entry["file"] == "benchmarks/configs/keye-vl-2.0-30b-a3b.json"
  assert len(entry["why"]) <= 200
  assert "8-chip deployment" in c["deployment"]
  assert c["vision_tower"] == "left out"
  assumed = " ".join(c["assumed"])
  for word in ("vision tower is LEFT OUT", "mrope_section [16, 24, 24]",
               "UNUSED under text", "RMSNorm over head_dim 128",
               "QUERY comes from the layer's normed input",
               "LayerNorm with scale and bias", "AS ROTATED",
               "ALL 64 dims", "16^-0.5 x 64^-0.5", "Hadamard",
               "q_chunk_size and kv_chunk_size 512", "BY TOKEN",
               "earlier position first", "softmax(z W_r)", "no shared expert",
               "N(0, 1/fan_in)"):
    assert word in assumed, word
  assert c["compute_dtype"] == "bfloat16" and c["float32_activations"] is False


def test_program_tree_is_the_programs_own(fam, toy):
  import jax
  import jax.numpy as jnp
  from flax.core import meta
  from tensorflowonspark_tpu.models import transformer as tfm
  cfg = fam.program_config(toy, 96)
  assert cfg.ffn_types == ("experts",) * 3
  assert (cfg.sparse_topk, cfg.index_heads, cfg.index_head_dim,
          cfg.experts_score, cfg.experts_shared) == (8, 2, 8, "softmax", 0)
  want = meta.unbox(jax.eval_shape(lambda: tfm.Transformer(cfg).init(
      jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
  got = fam.program_params(1, toy)
  assert jax.tree.structure(want) == jax.tree.structure(got)
  assert all(a.shape == b.shape for a, b in
             zip(jax.tree.leaves(want), jax.tree.leaves(got)))
  assert fam.param_count(toy) == sum(x.size for x in jax.tree.leaves(got))
  full = loader.load_json(needs.CONFIG)
  assert fam.param_count(full) == full["parameters_as_built"] == 659517696 \
      == needs.param_count()


def test_the_gates_configuration_is_the_cells(fam):
  """``tools/mosaic_gate.keye_cfg`` spells the configuration out by hand; it
  is what the family builds from the configuration and traffic files."""
  from tools import mosaic_gate
  full = {k: v for k, v in loader.load_json(needs.CONFIG).items()
          if k != "rehearse"}
  traffic = loader.load_json(TRAFFIC)
  assert (traffic["slots"], traffic["max_seq"]) == (
      mosaic_gate.KEYE_SLOTS, mosaic_gate.KEYE_MAX_SEQ)
  assert fam.program_config(full, traffic["max_seq"]) \
      == mosaic_gate.keye_cfg()


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
  # same mathematics, both float32, the same rows chosen: summation order
  assert float(jnp.abs(out - ref).max()) < 2e-4
  # and the reference without a piece of the mathematics is another model
  for control in ("no_select", "select_half", "no_renorm"):
    less = fam.reference_logits(weights, toks, toy, control)
    assert float(jnp.abs(less - ref).max()) > 0.1, control


def _spec(tmp_path, toy, control=False):
  return dict(cell="test", chips=1, config=toy, traffic=_rehearsed(TRAFFIC),
              seed=5, seconds=1.5, trace=False, rehearse=True,
              control=control, run_dir=str(tmp_path), t_start=0.0)


def test_rehearsal_is_correct_and_control_and_altered_token_are_not(
    tmp_path, monkeypatch, toy):
  """The serve runner's whole child in this process at the cell's rehearsal
  sizes: sound, it is ``correct``, counts the selection's rows and queries
  beside held assignments, and writes every leaf (three a layer) through the
  slab in place; the fp8 control's first tokens lie beyond the limit; with the
  served tokens altered where they are produced, ``correct`` comes out
  false."""
  from tensorflowonspark_tpu.serving import slots as slots_lib
  runner = loader.load_module("runners", TRAFFIC_RUNNER)
  spec = _spec(tmp_path, toy, control=True)
  limits = spec["traffic"]["limits"]
  path = os.path.join(str(tmp_path), "sound.json")
  runner.child_main(spec, path)
  rep = loader.load_json(path)
  checks = runner.checks_from(rep, limits)
  assert all(c["ok"] for c in checks) and len(checks) == 6
  assert checks[-1]["name"] == "served_over_control_gap_mean"
  assert rep["checked_tokens"] >= 10
  assert rep["control_gap_max"] > limits["served_logit_gap_max"]
  # the control reads the 2 of the 4 checked requests served the most tokens
  rows = rep["gap_by_request"]
  both = [r for r in rows if r["control_sum"] is not None]
  assert (len(rows), len(both)) == (4, 2)
  assert min(r["tokens"] for r in both) >= max(
      r["tokens"] for r in rows if r["control_sum"] is None)
  assert rep["controlled_tokens"] == sum(r["tokens"] for r in both)
  assert sum(r["tokens"] for r in rows) == rep["checked_tokens"]
  assert abs(sum(r["control_sum"] for r in both) / rep["controlled_tokens"]
             - rep["control_gap_mean"]) < 1e-6
  control = {c["name"]: c for c in runner.checks_from(dict(
      rep, served_gap_max=rep["control_gap_max"],
      served_gap_mean=rep["control_gap_mean"],
      controlled_served_gap_mean=rep["control_gap_mean"]), limits)}
  assert not control["served_logit_gap_mean"]["ok"]
  # the control in the program's place reads 1.0 by construction
  assert control["served_over_control_gap_mean"]["value"] == 1.0
  assert not control["served_over_control_gap_mean"]["ok"]
  # a run whose control read nothing cannot vouch
  blind = {c["name"]: c for c in runner.checks_from(
      dict(rep, control_gap_mean=None), limits)}
  assert not blind["served_over_control_gap_mean"]["ok"]
  d = rep["stats_delta"]
  assert 0 < d["moe_experts_touched"] <= d["moe_assignments_held"]
  assert d["slab_in_place"] == d["slab_dispatches"] > 0
  # 3 layers x three leaves written, one read, horizon 4, every dispatch
  # (the window may open or close between two counters)
  assert d["cursor_leaf_writes"] % (3 * 3 * 4) == 0
  assert abs(d["cursor_leaf_writes"] // (3 * 3 * 4)
             - d["decode_dispatches"]) <= 1
  assert d["decode_attn_reads"] * 3 == d["cursor_leaf_writes"]
  assert d["decode_attn_reads_sparse"] == d["decode_attn_reads"]
  assert d["index_rows_read"] == d["decode_attn_reads"] * 4 * 96
  assert d["decode_attn_reads_ragged"] == 0                 # the CPU
  # every prompt of the rehearsal's mix passes the toy topk of 8
  assert 0 < d["sparse_rows_kept"] < d["sparse_rows_candidate"]
  assert d["sparse_queries_limited"] == d["live_slot_steps"]
  assert 0 < d["sparse_prefill_limited"] < d["sparse_prefill_queries"]
  assert 0 < _read("sparse_rows_kept_share.keye", rep) < 100
  assert 50 < _read("sparse_queries_limited_share.keye", rep) < 100
  assert 0 < _read("index_rows_live_share.keye", rep) < 100
  assert 0 < _read("moe_held_assignments_per_token.keye", rep) < 2
  assert 0 < _read("moe_experts_touched_share.keye", rep) <= 100
  assert _read("prefill_tok_s.keye", rep) > 0
  # a step's time on the CPU is no device number: nothing is read from it,
  # and an untraced run has no kernel time
  assert _read("decode_step_needed_gb_s.keye", rep) is None
  for name in TRACE_READERS:
    assert _read(name, rep) is None
  assert _read("decode_step_needed_gb_s.keye", dict(
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


def test_the_control_reads_the_requests_served_the_most_tokens():
  runner = loader.load_module("runners", TRAFFIC_RUNNER)
  sample = [(None, [0] * n) for n in (5, 9, 2, 9, 7)]
  assert runner._most_served(sample, 3) == [1, 3, 4]     # the earlier first
  assert runner._most_served(sample, 1) == [1]
  assert runner._most_served(sample, 8) == [0, 1, 2, 3, 4]
  assert runner._most_served([], 3) == []
  # the ratio is of the controlled requests' own served tokens; a report of
  # serve_engine's own child has the control over all of them
  limits = dict(_rehearsed(TRAFFIC)["limits"])
  rep = dict(stats_all=dict(engine_restarts=0, replay_mismatches=0),
             checked_tokens=100, served_gap_max=0.0, served_gap_mean=0.5,
             control_gap_mean=1.0, controlled_served_gap_mean=0.01)
  assert runner.checks_from(rep, limits)[-1]["value"] == 0.01
  del rep["controlled_served_gap_mean"]
  assert runner.checks_from(rep, limits)[-1]["value"] == 0.5


def test_the_child_dies_with_the_parent_that_started_it(tmp_path):
  """A run cut from outside (its parent killed) leaves no child behind: the
  runner's child asks the kernel to be killed when its parent dies."""
  import signal
  import time
  code = (
      "import os, sys, time, multiprocessing\n"
      "sys.path.insert(0, %r)\n"
      "from benchmarks.lib import loader\n"
      "runner = loader.load_module('runners', %r)\n"
      "def child(ppid, path):\n"
      "  runner._die_with_parent(ppid)\n"
      "  open(path, 'w').write(str(os.getpid()))\n"
      "  time.sleep(120)\n"
      "if __name__ == '__main__':\n"
      "  p = multiprocessing.get_context('fork').Process(\n"
      "      target=child, args=(os.getpid(), sys.argv[1]))\n"
      "  p.start(); p.join()\n" % (ROOT, TRAFFIC_RUNNER))
  script, pid_file = tmp_path / "parent.py", tmp_path / "child.pid"
  script.write_text(code)
  parent = subprocess.Popen([sys.executable, str(script), str(pid_file)])
  try:
    deadline = time.time() + 60
    while not pid_file.exists() or not pid_file.read_text():
      assert time.time() < deadline and parent.poll() is None
      time.sleep(0.05)
    child = int(pid_file.read_text())
    os.kill(child, 0)                              # alive
    parent.send_signal(signal.SIGKILL)
    parent.wait(10)
    deadline = time.time() + 10
    while time.time() < deadline:
      try:
        os.kill(child, 0)
      except ProcessLookupError:
        break
      # a zombie waiting for init still answers: read its state
      try:
        with open("/proc/%d/stat" % child) as f:
          if f.read().rsplit(")", 1)[1].split()[0] == "Z":
            break
      except FileNotFoundError:
        break
      time.sleep(0.05)
    else:
      os.kill(child, signal.SIGKILL)
      raise AssertionError("the child outlived its parent")
  finally:
    if parent.poll() is None:
      parent.kill()


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
  """``benchmarks/tools/selection_controls.py`` at the rehearsal's sizes (its
  whole ``main``, as the builder calls it on the chip): the sound run is
  ``correct``; the reference WITHOUT the selection, the one that selects half,
  the one WITHOUT the router's renormalisation and the fp8 one, each in the
  program's place, are not, over all checked tokens and over those beyond
  position ``topk``."""
  from benchmarks.tools import selection_controls
  base = loader.load_module("runners", "serve_engine")
  # the tool replaces the runner's reference pass: put it back afterwards
  monkeypatch.setattr(base, "_reference_gaps", base._reference_gaps)
  assert selection_controls.main(["--seed", "5", "--seconds", "1.5",
                                  "--rehearse"]) == 0
  out = loader.load_json(os.path.join(
      ROOT, "chiprun_out", "selection_controls-%s-5.json" % CELL))
  assert out["sound"]["correct"] and not out["sound"]["failed"]
  assert out["sound"]["over_control"] == 0.0
  assert out["fp8"]["over_control"] == 1.0
  for control in ("no_select", "select_half", "no_renorm", "fp8"):
    assert not out[control]["correct"], out[control]
    assert "served_over_control_gap_mean" in out[control]["failed"]
    assert out[control]["beyond_topk"]["tokens"] > 0
    assert out[control]["beyond_topk"]["over_control"] > 0.05


def test_the_overlap_tool_reads_100_where_both_sides_are_float32():
  from benchmarks.tools import selection_overlap
  assert selection_overlap.main(["--seed", "3", "--rehearse"]) == 0
  out = loader.load_json(os.path.join(ROOT, "chiprun_out",
                                      "selection_overlap-3.json"))
  assert [row["share"] for row in out["layers"]] == [100.0] * 3
  assert all(row["queries"] == 56 for row in out["layers"])


def test_the_step_zero_tool_runs_at_tiny_sizes():
  from benchmarks.tools import index_select_time
  assert index_select_time.main(["--tiny"]) == 0
  out = loader.load_json(os.path.join(ROOT, "chiprun_out",
                                      "index_select_time.json"))
  names = [r["reading"] for r in out["readings"]]
  for word in ("select_threshold_", "select_lax_top_k_", "index_scores_",
               "decode_read_dense_masked", "decode_read_kernel_keep",
               "decode_read_gather_then_dense", "flash_keep_operand",
               "xla_masked"):
    assert any(word in n for n in names), word
  assert out["tiny"] and all(r["ms"] > 0 for r in out["readings"])


# -- what a step needs, and the readers ---------------------------------------


def test_needs_against_a_hand_count_at_the_published_sizes():
  """ISSUE 44's hand count: a layer's attention 18,874,624 parameters, its
  indexer 2,261,120, a layer outside its experts 21,401,984, an expert
  4,718,592 = 9.44 MB, a layer here 96,899,456, embedding, head and final norm
  78,120,960, the model 659,517,696 = 1.32 GB of bf16 matrices; a token's row
  2304 B a layer; the slab 7.25 GB at 16 x 32768, a prompt's row 0.453 GB."""
  z, by_part = needs.sizes(), needs.layer_params()
  assert by_part == dict(attention=18874624, indexer=2261120, expert=4718592,
                         outside_experts=21401984, layer=96899456,
                         ends=78120960)
  assert 6 * by_part["layer"] + by_part["ends"] == needs.param_count() \
      == 659517696
  assert z["expert_params"] * 2 == 9437184
  assert (z["kv_token_bytes"], z["index_token_bytes"], z["token_bytes"]) \
      == (2048, 256, 2304)
  assert z["layers"] * z["token_bytes"] == 13824
  assert needs.slab_bytes(16, 32768) == 7247757312
  assert needs.slab_bytes(12, 32768) == 5435817984
  assert needs.row_bytes(32768) == 452984832
  assert needs.weight_bytes() / 1e9 == pytest.approx(1.32, abs=0.01)
  assert needs.passed_bytes() / 1e9 == pytest.approx(0.34, abs=0.005)
  assert (needs.weight_bytes() - needs.passed_bytes()
          - 2 * z["embed_params"]) == 96 * 9437184              # 0.91 GB
  # a step: passed + 62 touched experts + 16 lanes at a mean cursor of 13,000
  step = needs.decode_step_bytes(16, 62, 16 * 13000)
  assert step == needs.passed_bytes() + 62 * 9437184 \
      + (16 * 13000 + 16) * 13824
  # a read of the chosen rows alone: every candidate's index key, the kept
  # rows' keys and values (both counted a layer)
  assert needs.chosen_rows_bytes(6 * 16 * 2048, 6 * 16 * 13001) \
      == 6 * 16 * (13001 * 256 + 2048 * 2048)
  # one call of the kernel over 208,000 live rows: 16 FLOP a byte, far under
  # the chip's ridge
  assert needs.decode_attention_bytes(208000) == 208000 * 2049
  assert needs.decode_attention_flops(208000) == 208000 * 32 * 2 * 256
  assert needs.decode_attention_flops(1) / needs.decode_attention_bytes(1) \
      == pytest.approx(8.0, abs=0.01)


def _report(**delta):
  d = dict(steps=1000, live_slot_steps=16000, live_context_tokens=208_000_000,
           moe_assignments_held=96000, moe_experts_touched=61800,
           t_decode_dispatch_s=2.0, t_decode_fetch_s=10.0,
           decode_attn_reads=6000, decode_attn_reads_ragged=6000,
           sparse_rows_kept=6 * 16000 * 2048,
           sparse_rows_candidate=6 * (208_000_000 + 16000),
           sparse_queries_limited=16000, sparse_prefill_queries=400_000,
           sparse_prefill_limited=330_000)
  d.update(delta)
  d.setdefault("decode_attn_reads_sparse", d["decode_attn_reads"])
  d.setdefault("index_rows_read", d["decode_attn_reads_sparse"] * 16 * 32768)
  return dict(stats_delta=d, slots=16,
              device=dict(platform="tpu", kind="TPU v5 lite"),
              trace_summary=dict(busy_s=2.5, op_group_seconds={
                  "%fusion": 0.6, "%convert_reduce_fusion": 0.30,
                  "%and_or_fusion": 0.02, "%compare_select_fusion": 0.03,
                  "%pad": 0.02}, kernels={
                      "%decode_attention": dict(seconds=0.250, calls=360.0),
                      "%expert_product": dict(seconds=0.050, calls=1080.0),
                      "%cursor_write": dict(seconds=0.01, calls=1080.0)}),
              requests=[dict(prompt_len=16384, started_at=10.0,
                             prefill_done_at=11.5),
                        dict(prompt_len=2048, started_at=11.0,
                             prefill_done_at=11.1),
                        dict(prompt_len=50, started_at=None,
                             prefill_done_at=None)])


def _read(name, report):
  return loader.load_module("layer_metrics", name).read(report)


def test_readers_arithmetic():
  rep = _report()
  assert _read("moe_held_assignments_per_token.keye", rep) \
      == pytest.approx(96000 / (16000 * 6)) == 1.0
  assert _read("moe_experts_touched_share.keye", rep) \
      == pytest.approx(100 * 61800 / (1000 * 6 * 16))
  assert _read("sparse_rows_kept_share.keye", rep) \
      == pytest.approx(100 * 2048 / 13001)
  assert _read("sparse_queries_limited_share.keye", rep) \
      == pytest.approx(100 * (16000 + 330000) / (16000 + 400000))
  # six reads a step, each the whole leaf of 16 x 32768 rows
  assert _read("index_rows_live_share.keye", rep) \
      == pytest.approx(100 * 13001 / 32768)
  assert _read("prefill_tok_s.keye", rep) == pytest.approx(18432 / 1.6)
  nbytes = needs.decode_step_bytes(16, 61.8, 208000)
  # 12 ms a step on the loop thread's clock: GB a second, no peak in it
  assert _read("decode_step_needed_gb_s.keye", rep) \
      == pytest.approx(nbytes / 1e9 / 0.012)
  # 360 calls traced, each over 208,000 live rows of 2049 B at 819 GB/s
  least = 360 * 208000 * 2049 / 819e9
  assert 2049 / 819e9 > 32 * 2 * 256 / 197e12                # bytes, by far
  assert _read("decode_attention_roofline.keye", rep) \
      == pytest.approx(100 * least / 0.250)
  # 1080 calls, each 61800 / 6000 = 10.3 touched experts' one matrix
  least = 1080 * 10.3 * 4718592 / 3 * 2 / 819e9
  assert _read("expert_product_roofline.keye", rep) \
      == pytest.approx(100 * least / 0.050)
  # the selection's groups by name over the busy seconds; others are not its
  assert _read("index_select_device_share.keye", rep) \
      == pytest.approx(100 * (0.30 + 0.02 + 0.03) / 2.5)


def test_both_rooflines_stay_under_100_when_calls_read_whole_blocks():
  """A fixture whose calls ran AT the HBM peak over the bytes they really
  moved: every slot's live rows rounded UP to whole blocks of 128 with the
  keep rows' four bytes an entry, and every held expert's matrix streamed
  whether touched or not. The readers count live rows and touched experts
  only, so both read under 100."""
  lanes, cursor, steps = 16, 13001, 1000
  blocks = -(-cursor // 128) * 128
  calls = 6 * 60
  seconds = calls * lanes * blocks * (2048 + 4) / 819e9
  rep = _report(steps=steps, live_slot_steps=lanes * steps,
                live_context_tokens=lanes * cursor * steps)
  rep["trace_summary"]["kernels"]["%decode_attention"] = dict(
      seconds=seconds, calls=float(calls))
  rep["trace_summary"]["kernels"]["%expert_product"] = dict(
      seconds=3 * calls * 16 * 4718592 / 3 * 2 / 819e9, calls=3.0 * calls)
  got = _read("decode_attention_roofline.keye", rep)
  assert 95.0 < got < 100.0, got
  got = _read("expert_product_roofline.keye", rep)
  assert 60.0 < got < 70.0, got                      # 10.3 of 16 touched
  # a device that is not in the table is an error, not a default
  with pytest.raises(ValueError, match="unknown device_kind"):
    _read("decode_attention_roofline.keye",
          dict(rep, device=dict(platform="tpu", kind="TPU v9")))


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_from_a_program_without_the_counters(name):
  """The parent of PR 44 has no ``sparse_rows_kept``, an idle window no step,
  an untraced run no kernel, a program whose reads stayed dense no ragged
  read: the reader returns nothing and does not raise."""
  assert _read(name, {}) is None
  assert _read(name, dict(stats_delta=dict(
      steps=8, live_slot_steps=20, live_context_tokens=100,
      moe_assignments_held=5, moe_experts_touched=4, decode_attn_reads=56,
      decode_attn_reads_ragged=56, t_decode_dispatch_s=0.1,
      t_decode_fetch_s=0.1), requests=[])) is None
  idle = _report(steps=0, live_slot_steps=0)
  idle["requests"] = []
  assert _read(name, idle) is None
  if name in TRACE_READERS:
    assert _read(name, dict(_report(), trace_summary=None)) is None
    assert _read(name, dict(_report(), trace_summary=dict(kernels={}))) is None
    # a traced program WITHOUT a selection (the parent): busy, but no group
    assert _read(name, dict(_report(), trace_summary=dict(
        busy_s=2.0, kernels={}, op_group_seconds={"%fusion": 1.0}))) is None
  if name == "decode_attention_roofline.keye":
    assert _read(name, _report(decode_attn_reads_ragged=0)) is None


def test_the_new_entries_keep_the_contract():
  """Looked up BY NAME: a later PR appends after them."""
  b = loader.load_json(os.path.join(ROOT, "BENCHMARK.json"))
  cell = [w for w in b["workloads"] if w["name"] == CELL]
  assert len(cell) == 1 and cell[0]["chips"] == 1
  assert (cell[0]["config"], cell[0]["traffic"]) == (
      "keye-vl-2.0-30b-a3b", "serve-backlog-32k-sparse")
  assert len(cell[0]["why"]) <= 200
  by_name = {m["name"]: m for m in b["per_layer"]}
  for name in READERS:
    m = by_name[name]
    assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
    assert m["layer"] == ("kernels" if "roofline" in name
                          else "model step, serving")
    assert m["unit"] == "%" or "roofline" not in name
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert os.path.exists(os.path.join(ROOT, "benchmarks", "layer_metrics",
                                       name + ".py"))
  for name in TRACE_READERS:
    assert (by_name[name]["unit"], by_name[name]["source"]) \
        == ("%", "device_trace")
    assert "_roofline" in name or name == "index_select_device_share.keye"
  serve = [m for m in b["end_to_end"] if m["name"] == "serve_tok_s"][0]
  assert CELL in serve["workloads"]
  # every shared metric the DeepSeek cell is listed under lists this cell too
  shared = {m["name"] for m in b["per_layer"]
            if "deepseek-v3-serve-backlog" in m.get("workloads", ())
            and not m["name"].endswith(".deepseek")}
  assert len(shared) == 21 and "expert_product_kernel_share.backlog" in shared
  for name in shared:
    assert CELL in by_name[name]["workloads"], name
  assert len(b["workloads"]) <= 24 and all(w["chips"] == 1
                                           for w in b["workloads"])
  assert len(json.dumps(b, indent=1)) < 64 * 1024


def test_the_traffic_file_is_the_issues():
  t = loader.load_json(TRAFFIC)
  assert (t["runner"], t["loop"], t["max_seq"]) \
      == (TRAFFIC_RUNNER, "closed", 32768)
  # step zero's rule: 16 slots with 32 clients, else 12 with 24
  assert (t["slots"], t["clients"]) in ((16, 32), (12, 24))
  mix = t["mix"]
  assert mix["prompt_lens"] == [2048, 4096, 8192, 16384, 24576, 30720]
  assert mix["prompt_weights"] == [3, 5, 6, 6, 4, 2]
  assert mix["output_lens"] == [128, 256, 512, 1024]
  assert mix["output_weights"] == [6, 8, 8, 4]
  assert (mix["pool"], mix["max_total"], mix["order"]) == (32, 32768, "fixed")
  assert (t["ramp_seconds"], t["drain_seconds"], t["check_requests"],
          t["trace_seconds"]) == (20, 0, 8, 3.0)
  from benchmarks.lib import traffic
  pool = traffic.size_pool(mix)
  assert len(pool) == 32 and all(p + o <= 32768 for p, o in pool)
  # the issue's means: 12,800 prompt tokens (the pool of 32 rounds the
  # weights of 26), a few hundred output tokens
  assert 11000 < sum(p for p, _ in pool) / 32 < 14500
  assert 350 < sum(o for _, o in pool) / 32 < 500
  assert t["rehearse"]["slots"] == 4 and t["rehearse"]["max_seq"] == 96
  assert min(t["rehearse"]["mix"]["prompt_lens"]) > 8     # past the toy topk
  assert set(t["limits"]) == {"checked_tokens_min", "served_logit_gap_max",
                              "served_logit_gap_mean_max",
                              "served_over_control_gap_mean_max"}
  assert set(t["rehearse"]["limits"]) == set(t["limits"])
  assert (t["control_requests"], t["rehearse"]["control_requests"]) == (5, 2)
  for key in ("slots_why", "limits_why", "rehearse_why", "runner_why",
              "control_requests_why"):
    assert len(t[key]) > 100 and "TO BE FILLED" not in t[key], key
