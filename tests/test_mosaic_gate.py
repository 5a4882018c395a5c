"""The deviceless Mosaic-lowering gate (tools/mosaic_gate.py).

Interpret-green Pallas kernels can be rejected by real Mosaic lowering
("XLA layout ... does not match Mosaic layout", a block off the tiling, a
kernel GSPMD cannot partition). The gate AOT-compiles kernels and whole
programs against a DESCRIBED TPU topology (jax.experimental.topologies) —
libtpu's real compiler, no chip attached — so what the compiler refuses
costs no chip time. These tests assert the gate is wired correctly AND has
teeth (a Mosaic-invalid kernel turns red), and keep chip_smoke.py's
programs compiling at their real shapes.

This is the ONE test file that loads the TPU compiler: only one process at
a time may hold libtpu, so every test here takes the module-scoped ``topo``
fixture (never autouse, nothing at import / in a skipif / in parametrize
arguments / in conftest), compiles in the test's own process, and no second
file may do the same (it could land on another xdist worker).
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def topo():
  """The described v5e:2x2 topology; skips from HERE where it cannot be
  described (no local libtpu, or another process holds its lock)."""
  try:
    from tools.mosaic_gate import _topology
    return _topology("v5e:2x2")
  except Exception as e:  # noqa: BLE001 - gate unavailable on this host
    pytest.skip("no v5e:2x2 topology can be described here: %r" % (e,))


def test_gate_green_on_production_kernels(topo):
  """A fused-backward flash target (short-seq clamp path) and the fused
  LayerNorm compile through real Mosaic lowering, devicelessly."""
  from tools.mosaic_gate import run_gate
  results = run_gate(["layer_norm", "flash_short_seq_bwd"])
  assert all(r["ok"] for r in results), results


def test_gate_red_on_mosaic_invalid_kernel(topo):
  """A kernel that interpret mode happily runs (1-D iota) must FAIL the
  deviceless compile — proof the gate exercises real Mosaic lowering, not
  the interpret emulation."""
  import numpy as np
  import jax
  import jax.numpy as jnp
  from jax.experimental import pallas as pl
  from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

  mesh = Mesh(np.array(topo.devices[:1]), ("one",))

  def kern(x_ref, o_ref):
    o_ref[...] = x_ref[...] + jax.lax.iota(jnp.float32, 128)

  def call(x):
    return pl.pallas_call(
        kern, out_shape=jax.ShapeDtypeStruct((128,), jnp.float32))(x)

  x = jax.ShapeDtypeStruct((128,), jnp.float32)
  # interpret mode: green (the blind spot the gate exists to close)
  jax.jit(lambda x: pl.pallas_call(
      kern, out_shape=jax.ShapeDtypeStruct((128,), jnp.float32),
      interpret=True)(x)).lower(x).compile()
  # real Mosaic lowering: red — and specifically the Mosaic verifier
  # rejecting the op, not some unrelated topology/sharding failure
  f = jax.jit(call, in_shardings=(NamedSharding(mesh, P()),))
  with pytest.raises(Exception, match=r"tpu\.iota|[Mm]osaic"):
    f.lower(x).compile()


def test_int8_cache_never_materializes_f32(topo, monkeypatch):
  """The int8 KV cache's HBM claim, checked on COMPILED TPU HLO: scales
  apply to k-indexed tensors (scores/probs), so the only cache-shaped
  producers are bare converts fused into the dots — no top-level
  (materialized) f32 buffer of the cache shape may exist, else decode
  would write+reread a dequantized copy and invert the feature."""
  import re
  monkeypatch.setenv("TOS_PALLAS_INTERPRET", "0")
  from tools.mosaic_gate import TARGETS
  fn, args = TARGETS["serving_decode_int8"]()
  hlo = fn.lower(*args).compile().as_text()
  # per-shard cache shape for the target's config: batch 4 over data=2,
  # max_seq 64, the folded kv_heads * head_dim axis (2 x 128/4 = 64) over
  # tensor=2; neither that shape nor a 4-D view of it may exist in f32
  cache_shape = "2,64,32"
  bad = [l for l in hlo.splitlines()
         if "f32[%s]" % cache_shape in l or "f32[2,64,1,32]" in l]
  assert not bad, "dequantized f32 cache tensors:\n" + "\n".join(bad[:4])
  assert re.search(r"s8\[%s\]" % cache_shape, hlo)   # the cache IS int8


def test_gate_full_train_step_compiles(topo, monkeypatch):
  """The dryrun-config 8-chip training step (ring + GQA flash + fused
  LayerNorm + remat) Mosaic-compiles on a v5e:2x4 topology with abstract
  state — the multi-chip production path is compile-checked without any
  device."""
  monkeypatch.setenv("TOS_PALLAS_INTERPRET", "0")
  from tools.mosaic_gate import run_gate
  results = run_gate(["train_step"])
  assert results[0]["ok"], results


# chip_smoke.py's programs at its real shapes (12 layers / 768 / 12x64 /
# 3072 / vocab 32000, 16 x 1024): names only here — the targets themselves
# are built inside the test, after the fixture
SMOKE_KERNELS = ("smoke_flash_fwd", "smoke_flash_fused_bwd",
                 "smoke_layer_norm")
SMOKE_SERVING = ("smoke_insert", "smoke_step_many", "smoke_paged_insert",
                 "smoke_paged_step_many") + tuple(
                     "smoke_prefill_%d" % b
                     for b in (512, 256, 128, 64, 32, 16))


def _gate_one(name, monkeypatch):
  monkeypatch.setenv("TOS_PALLAS_INTERPRET", "0")
  from tools.mosaic_gate import run_gate
  (res,) = run_gate([name])
  assert res["ok"], res
  return res


@pytest.mark.parametrize("name", SMOKE_KERNELS)
def test_smoke_kernels_compile_at_real_shapes(topo, monkeypatch, name):
  """Flash fwd / fused bwd at B16 S1024 H12 D64 bf16 and the fused
  LayerNorm fwd+bwd at 16384 x 768 — head_dim 64, the width the smoke's
  model really has (the older targets use 128)."""
  res = _gate_one(name, monkeypatch)
  assert res["tpu_custom_calls"] >= 1, res


@pytest.mark.parametrize("name", SMOKE_SERVING)
def test_smoke_serving_programs_compile(topo, monkeypatch, name):
  """Every program serving/slots.py jits at the smoke's widths: each
  prefill bucket, insert and step_many for the engine's default
  (contiguous) layout, insert_pages and step_many for the paged one."""
  from tools.mosaic_gate import V5E_HBM_BYTES
  res = _gate_one(name, monkeypatch)
  assert res["device_bytes"] < V5E_HBM_BYTES, res
  if "insert" not in name:
    # the fused LayerNorm rides every forward; a data-movement-only
    # program (insert) has no kernel to carry
    assert res["tpu_custom_calls"] >= 1, res


@pytest.mark.parametrize("name", ("smoke_insert", "smoke_step_many"))
def test_smoke_slab_programs_run_in_place(topo, monkeypatch, name):
  """The slab is ONE buffer in ONE layout: the program aliases the whole
  slab it was given (``alias`` = the slab's bytes), keeps under a tenth of
  it in temporaries (beside the bf16 copies of the smoke's f32 parameters,
  at most half their bytes, which the compiler hoists out of the scan),
  and its entry computation copies nothing of a slab leaf's shape: no
  relayout into a padded 4-D layout and back (PERF.md section 6, PR 25:
  40% of the serving cells' device time before it; this program's temp
  was 434 MB then, 2.9 slabs)."""
  import jax
  from tools.mosaic_gate import TARGETS, compiled_facts
  monkeypatch.setenv("TOS_PALLAS_INTERPRET", "0")
  fn, args = TARGETS[name]()
  def nbytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))

  slabs, params = (args[0], ()) if name == "smoke_insert" else args[1::-1]
  leaves = jax.tree.leaves(slabs)
  slab_bytes = nbytes(slabs)
  big = {"%s[%s]" % ({"bfloat16": "bf16"}[x.dtype.name],
                      ",".join(map(str, x.shape)))
         for x in leaves if x.ndim == 3}
  assert len(big) == 1, big
  facts = compiled_facts(fn.lower(*args).compile())
  mb = facts["memory_bytes"]
  # the runtime pads each tiny cursor leaf to a tile: a few KB over
  assert slab_bytes <= mb["alias"] < 1.001 * slab_bytes, (mb, slab_bytes)
  assert mb["temp"] < 0.1 * slab_bytes + nbytes(params) / 2, (mb, slab_bytes)
  assert not big & set(facts["entry_copies"]), facts["entry_copies"]


def test_gpt2l_step_many_keeps_the_slab_in_hbm_and_in_place(topo,
                                                            monkeypatch):
  """The benchmark's serving step at its real size (gpt2-large, 16 slots
  x 1024, horizon 4): the 3.02 GB slab is aliased whole, temporaries stay
  under 0.5 GB (7.31 GB before PR 25), the entry computation copies no
  slab leaf, and the compiler copies NONE of the 72 leaves back from fast
  memory: attention reads the cache as it was before the step's write, so
  the staged copy of a leaf is read-only (71 of 72 came back whole, 3 GB a
  step, while the write came first), and the write itself is
  ``ops.cursor_write``'s kernel, pinned to the leaf in HBM (2 leaves came
  back beside XLA's loop; 68 beside a kernel free to run on the staged
  copy). One ``while`` is left, the horizon's scan (73 with a loop of 16
  update-slices a leaf), and the kernel is in: 72 calls beside the 73 of
  the fused LayerNorm (PERF.md section 6, PR 29). Since PR 31 the read is a
  kernel too, ``ops.decode_attention``, one call a layer: it brings the
  live blocks of both leaves from HBM itself, BEFORE the in-place write of
  the same leaves, and the compiler neither stages a leaf for it nor copies
  one to keep the old value; temporaries did not grow (64 MB before)."""
  res = _gate_one("gpt2l_step_many", monkeypatch)
  mb = res["memory_bytes"]
  slab_bytes = 72 * 16 * 1024 * 1280 * 2
  assert slab_bytes <= mb["alias"] < 1.001 * slab_bytes, mb
  assert mb["temp"] < 0.5e9, mb
  leaf = "bf16[16,1024,1280]"
  assert leaf not in res["entry_copies"], res["entry_copies"]
  assert leaf not in res["copies_back_to_hbm"], res["copies_back_to_hbm"]
  assert res["while_loops"] == 1, res
  assert res["tpu_custom_calls"] == 72 + 73 + 36, res
  assert mb["temp"] < 70e6, mb


@pytest.mark.parametrize("name,slots,max_seq,width", [
    ("decode_attention", 16, 1024, 1280),
    ("decode_attention_ouro", 8, 512, 2048)])
def test_decode_attention_reads_beside_the_in_place_write(
    topo, monkeypatch, name, slots, max_seq, width):
  """``ops.decode_attention`` and ``ops.cursor_write`` on K and V of one
  layer at the benchmark's widths (gpt2-large: 16 x 1024 x 20 heads of 64,
  a head half a vreg wide; Ouro: 8 x 512 x 16 heads of 128), the leaves
  donated: the kernel's hand-driven block DMAs, its dynamic trip count and
  its folded output lower through Mosaic; both leaves are aliased whole
  onto the write's results although the read takes them as they were;
  nothing of a leaf's shape is copied at the program's edge or back from
  fast memory; no temporary exists."""
  res = _gate_one(name, monkeypatch)
  leaf_bytes = slots * max_seq * width * 2
  mb = res["memory_bytes"]
  assert 2 * leaf_bytes <= mb["alias"] < 1.001 * 2 * leaf_bytes, mb
  assert mb["temp"] == 0, mb
  leaf = "bf16[%d,%d,%d]" % (slots, max_seq, width)
  assert leaf not in res["entry_copies"], res["entry_copies"]
  assert leaf not in res["copies_back_to_hbm"], res["copies_back_to_hbm"]
  assert res["tpu_custom_calls"] == 3 and res["while_loops"] == 0, res


def test_cursor_write_compiles_in_hbm_and_in_place(topo, monkeypatch):
  """``ops.cursor_write`` alone at the benchmark's widths (K and V of one
  gpt2-large layer, 16 slots x 1024 x 1280 bf16, donated): the aligned
  16-row tile DMA lowers through Mosaic (a one-row DMA of a packed leaf
  does not), both leaves are aliased whole, nothing of a leaf's shape is
  copied at the program's edge or back from fast memory, and no
  temporary exists: the only traffic is the tiles."""
  res = _gate_one("cursor_write", monkeypatch)
  leaf_bytes = 16 * 1024 * 1280 * 2
  mb = res["memory_bytes"]
  assert 2 * leaf_bytes <= mb["alias"] < 1.001 * 2 * leaf_bytes, mb
  assert mb["temp"] == 0, mb
  leaf = "bf16[16,1024,1280]"
  assert leaf not in res["entry_copies"], res["entry_copies"]
  assert leaf not in res["copies_back_to_hbm"], res["copies_back_to_hbm"]
  assert res["tpu_custom_calls"] == 2 and res["while_loops"] == 0, res


def test_gpt2l_padded_prefill_projects_one_row(topo, monkeypatch):
  """The benchmark's largest prefill program at its real size (gpt2-large,
  a padded 512-token chunk into a 1024-long row): it fits the chip, the
  flash branch and the fused LayerNorm are in, and only the last REAL row
  reaches the 50257-wide head: no [512, vocab] block of logits exists
  anywhere in the compiled program (the exact plan's program, which slices
  the logits after the head, builds a 51 MB one)."""
  import re
  monkeypatch.setenv("TOS_PALLAS_INTERPRET", "0")
  from tools.mosaic_gate import TARGETS, V5E_HBM_BYTES, compiled_facts
  fn, args = TARGETS["gpt2l_prefill_512"]()
  compiled = fn.lower(*args).compile()
  facts = compiled_facts(compiled)
  assert facts["device_bytes"] < V5E_HBM_BYTES, facts
  assert facts["memory_bytes"]["temp"] < 0.3e9, facts
  assert facts["tpu_custom_calls"] >= 36, facts
  text = compiled.as_text()
  assert "50257" in text
  assert not re.search(r"\[(1,)?512,50257\]", text)


def test_kimi_linear_step_many_keeps_every_kind_of_leaf_in_place(
    topo, monkeypatch):
  """The cell kimi-linear-serve-backlog's decode step at its real size (27
  layers at published widths, float32 activations on bf16 matrices, 48
  slots x 4096, horizon 4): ONE slab of 20 float32 KDA states (100 MB
  each), 20 convolution tails and 7 bf16 latent caches (lane-dense: 576
  padded to 640) is aliased whole; with 8.6 GB of
  weights beside it the program fits the chip; temporaries stay under 0.5
  GB; the entry computation copies no slab leaf (a 576-wide latent leaf was
  kept transposed and copied in and out, 14 x 226 MB a dispatch); the
  latent cache never comes back from fast memory (absorbed decode reads it
  as it was before the step's write, and ``ops.cursor_write`` writes its
  row in HBM: one ``while`` is left, the horizon's scan), and at most a few
  of the states do; the grouped expert products are kernels."""
  from tools.mosaic_gate import V5E_HBM_BYTES
  res = _gate_one("serving_decode_kimi_linear", monkeypatch)
  mb = res["memory_bytes"]
  state, tail, latent = (48 * 32 * 128 * 128 * 4, 48 * 3 * 12288 * 4,
                         48 * 4096 * 640 * 2)
  slab_bytes = 20 * (state + tail) + 7 * latent
  assert slab_bytes <= mb["alias"] < 1.001 * slab_bytes, mb
  assert mb["temp"] < 0.5e9, mb
  assert res["device_bytes"] < 0.85 * V5E_HBM_BYTES, res["device_bytes"]
  leaves = ("f32[48,32,128,128]", "f32[48,3,12288]", "bf16[48,4096,640]")
  assert not set(leaves) & set(res["entry_copies"]), res["entry_copies"]
  back = res["copies_back_to_hbm"]
  assert leaves[2] not in back and back.get(leaves[0], 0) <= 8, back
  assert back.get(leaves[1], 0) <= 2, back
  assert res["while_loops"] == 1, res
  assert res["tpu_custom_calls"] >= 26 * 3 + 7, res["tpu_custom_calls"]


@pytest.mark.parametrize("bucket,temp_max", [(512, 1.5e9), (256, 1.0e9)])
def test_kimi_linear_padded_prefill_fits_beside_the_slab(
    topo, monkeypatch, bucket, temp_max):
  """The same cell's two largest prefill programs under the padded plan (a
  chunk of 512 or 256 tokens with a traced true length: the 20 KDA layers
  mask the padding out of their float32 state and take the convolution
  tail where the real tokens end): each fits beside the resident 3.92 GB
  slab, takes no more than the exact plan's 512-token program did (10.18
  GB, 1.39 of it temporaries; 10.15 / 9.62 when written), keeps its 26
  grouped expert products as kernels, scans the blocks of 64 (one ``while``
  a KDA layer), and only the last REAL row reaches the 20480-wide head: no
  [bucket, vocab] block of logits exists in the compiled program."""
  import re
  monkeypatch.setenv("TOS_PALLAS_INTERPRET", "0")
  from tools.mosaic_gate import TARGETS, V5E_HBM_BYTES, compiled_facts
  fn, args = TARGETS["kimi_linear_prefill_%d" % bucket]()
  compiled = fn.lower(*args).compile()
  facts = compiled_facts(compiled)
  state, tail, latent = (48 * 32 * 128 * 128 * 4, 48 * 3 * 12288 * 4,
                         48 * 4096 * 640 * 2)
  slab_bytes = 20 * (state + tail) + 7 * latent
  assert facts["device_bytes"] < 10.2e9, facts
  assert facts["device_bytes"] + slab_bytes < 0.85 * V5E_HBM_BYTES, facts
  assert facts["memory_bytes"]["temp"] < temp_max, facts
  assert facts["tpu_custom_calls"] >= 26 * 3, facts
  assert facts["while_loops"] == 20, facts
  text = compiled.as_text()
  assert "20480" in text
  assert not re.search(r"\[(1,)?%d,20480\]" % bucket, text)


def test_looped_step_many_keeps_a_cache_a_pass_in_place(topo, monkeypatch):
  """The cell ouro-serve-backlog's decode step at its published widths and
  its 4 passes, 8 slots x 512, horizon 4, with 4 of the 48 layers (the
  whole program compiles for minutes; ``python -m tools.mosaic_gate
  --targets serving_decode_ouro`` is that compile): each pass of each layer
  owns a K and a V leaf, 32 leaves of 8 x 512 x 2048 here, all aliased,
  none copied at the program's edge or back from fast memory, every cursor
  write ``ops.cursor_write``'s kernel and every read
  ``ops.decode_attention``'s, one a pass of a layer (the model's norms are
  RMSNorms, no kernel of their own: 32 + 16 custom calls), one ``while``
  (the horizon's scan), the passes unrolled inside it; temporaries did not
  grow with the read kernel (116 MB before PR 31)."""
  res = _gate_one("serving_decode_ouro_4_layers", monkeypatch)
  mb = res["memory_bytes"]
  slab_bytes = 4 * 4 * 2 * 8 * 512 * 2048 * 2
  assert slab_bytes <= mb["alias"] < 1.001 * slab_bytes, mb
  leaf = "bf16[8,512,2048]"
  assert leaf not in res["entry_copies"], res["entry_copies"]
  assert leaf not in res["copies_back_to_hbm"], res["copies_back_to_hbm"]
  assert res["while_loops"] == 1, res
  assert res["tpu_custom_calls"] == 32 + 16, res
  assert mb["temp"] < 120e6, mb


def test_ring_step_many_keeps_rings_and_the_whole_context_leaf_in_place(
    topo, monkeypatch):
  """The cell trinity-serve-backlog's decode step at its real size (1 dense
  + 4 expert layers at published widths, 32 held experts a layer, 24 slots x
  16384, horizon 4): ONE slab of a whole-context leaf pair (24 x 16384 x
  1024) and four ring pairs (24 x 4096 x 1024), 3.22 GB, is aliased whole;
  with 8.64 GB of weights beside it the program fits the chip; no leaf of
  either length is copied at the program's edge or comes back from fast
  memory (the kernel reads a ring as it was, its skipped row included, and
  the cursor write lands in row ``cursor % 4096`` in HBM); one ``while`` is
  left, the horizon's scan; 10 cursor writes and 5 attention reads a step
  are kernels."""
  from tools.mosaic_gate import V5E_HBM_BYTES
  res = _gate_one("serving_decode_trinity", monkeypatch)
  mb = res["memory_bytes"]
  slab_bytes = 2 * 24 * 1024 * 2 * (16384 + 4 * 4096)
  assert slab_bytes <= mb["alias"] < 1.001 * slab_bytes, mb
  assert mb["temp"] < 0.6e9, mb
  assert res["device_bytes"] < 0.8 * V5E_HBM_BYTES, res["device_bytes"]
  for leaf in ("bf16[24,16384,1024]", "bf16[24,4096,1024]"):
    assert leaf not in res["entry_copies"], res["entry_copies"]
    assert leaf not in res["copies_back_to_hbm"], res["copies_back_to_hbm"]
  assert res["while_loops"] == 1, res
  assert res["tpu_custom_calls"] >= 10 + 5, res["tpu_custom_calls"]


@pytest.mark.parametrize("bucket,temp_max", [(512, 0.2e9), (1024, 0.2e9),
                                             (2048, 0.4e9)])
def test_later_prefill_chunk_of_a_long_row_builds_no_score_tensor(
    topo, monkeypatch, bucket, temp_max):
  """The same cell's three largest prefill chunks (the ladder of a 16384-row
  tops out at 2048 tokens) into a positional row of 16384 (one program for a
  cursor at 0 and above it): the later chunk attends the row in blocks
  through the flash kernel, so the dense branch's float32 scores of chunk x
  48 x 16384 (1.6 GB at 512 tokens, several times over) do not exist:
  temporaries stay under 0.2 GB (0.4 at 2048 tokens: 0.064 / 0.118 / 0.313
  when written), and the program fits beside the resident 3.22 GB slab."""
  from tools.mosaic_gate import V5E_HBM_BYTES
  res = _gate_one("trinity_prefill_%d" % bucket, monkeypatch)
  mb = res["memory_bytes"]
  assert mb["temp"] < temp_max, mb
  slab_bytes = 2 * 24 * 1024 * 2 * (16384 + 4 * 4096)
  assert res["device_bytes"] + slab_bytes < 0.85 * V5E_HBM_BYTES, res
  # the flash kernel a layer (first chunk) and again a layer (later chunks)
  assert res["tpu_custom_calls"] >= 10, res["tpu_custom_calls"]


def test_mimo_step_many_keeps_four_leaf_shapes_in_place(topo, monkeypatch):
  """The cell mimo-serve-backlog's decode step at its real size (1 dense + 6
  expert layers at published widths, 16 held experts a layer, 48 slots x
  16384, horizon 4): ONE slab of two whole-context leaf pairs (48 x 16384 x
  768 K and x 512 V) and five ring pairs (48 x 128 x 1536 and x 1024), 4.18
  GB, is aliased whole; with 6.87 GB of weights beside it the program fits the
  chip; no leaf of any of the FOUR shapes is copied at the program's edge or
  comes back from fast memory; one ``while`` is left, the horizon's scan; all
  7 attention reads a step took the kernel that stops at the cursor (keys of
  192 against values of 128, the 5 over a ring counted as such) and all 14
  leaf writes the DMA kernel: a read fallen to the dense contraction or a
  leaf copied fails HERE and not in a chip run."""
  from tools.mosaic_gate import V5E_HBM_BYTES, mimo_decoder
  res = _gate_one("serving_decode_mimo", monkeypatch)
  mb = res["memory_bytes"]
  slab_bytes = 48 * (2 * 2560 * 16384 + 5 * 5120 * 128)
  assert slab_bytes <= mb["alias"] < 1.001 * slab_bytes, mb
  assert mb["temp"] < 0.3e9, mb
  assert res["device_bytes"] < 0.7 * V5E_HBM_BYTES, res["device_bytes"]
  for leaf in ("bf16[48,16384,768]", "bf16[48,16384,512]",
               "bf16[48,128,1536]", "bf16[48,128,1024]"):
    assert leaf not in res["entry_copies"], res["entry_copies"]
    assert leaf not in res["copies_back_to_hbm"], res["copies_back_to_hbm"]
  assert res["while_loops"] == 1, res
  assert res["tpu_custom_calls"] >= 14 + 7, res["tpu_custom_calls"]
  # which lowering each read and write took: the trace-time tallies of the
  # same program (lowering alone, seconds)
  dec, params, _, slabs = mimo_decoder()
  from tools.mosaic_gate import _step_many_target
  fn, args = _step_many_target(dec, params, slabs)
  fn.lower(*args)
  assert dec.attn_reads[4] == (7 * 4, 7 * 4, 5 * 4)
  assert dec.cursor_writes[4] == (14 * 4, 14 * 4)


@pytest.mark.parametrize("bucket,temp_max", [(2048, 0.9e9), (256, 0.2e9)])
def test_mimo_prefill_chunk_builds_no_score_tensor(topo, monkeypatch, bucket,
                                                   temp_max):
  """The same cell's largest prefill chunk (2048 tokens) and its 256-token
  one into a positional row of 16384 (0.50 GB: leaves of four widths; one
  program for a cursor at 0 and above it): the first chunk attends itself
  and a later chunk the row in blocks through the flash FORWARD at keys of 192
  / values of 128, so the dense branch's float32 scores of chunk x 64 x 16384
  (8.6 GB at 2048 tokens) do not exist: temporaries stay under 0.9 GB (0.67 /
  0.03 when written), and the program fits beside the resident slab of 48
  slots (4.18 GB)."""
  from tools.mosaic_gate import V5E_HBM_BYTES
  res = _gate_one("mimo_prefill_%d" % bucket, monkeypatch)
  mb = res["memory_bytes"]
  assert mb["temp"] < temp_max, mb
  slab_48 = 48 * (2 * 2560 * 16384 + 5 * 5120 * 128)
  assert res["device_bytes"] + slab_48 < 0.8 * V5E_HBM_BYTES, res
  # the flash kernel a layer (first chunk) and again a layer (later chunks)
  assert res["tpu_custom_calls"] >= 14, res["tpu_custom_calls"]


def test_deepseek_step_many_reads_each_latent_leaf_once_in_place(
    topo, monkeypatch):
  """The cell deepseek-v3-serve-backlog's decode step at its real size (1
  dense + 4 expert layers at published widths, 128 heads, 16 held experts a
  layer, 24 slots x 16384, horizon 4): ONE slab of five latent leaves of 24 x
  16384 x 640 (2.52 GB) is aliased whole; with 9.15 GB of weights beside it
  the program fits the chip; no leaf is copied at the program's edge or comes
  back from fast memory; one ``while`` is left, the horizon's scan; all 5
  reads a step took the kernel that stops at the cursor (handed the leaf as K
  and as V) and all 5 leaf writes the DMA kernel: a read fallen to the dense
  contraction over all 16384 rows (2.6 ms a layer at 32 slots) fails HERE and
  not in a chip run."""
  import tools.mosaic_gate as gate
  from tools.mosaic_gate import V5E_HBM_BYTES
  # the decoder the gate builds and lowers: its tallies are that lowering's
  made, build = [], gate.deepseek_decoder
  monkeypatch.setattr(gate, "deepseek_decoder",
                      lambda *a: made.append(build(*a)) or made[-1])
  res = _gate_one("serving_decode_deepseek", monkeypatch)
  mb = res["memory_bytes"]
  slab_bytes = 24 * 5 * 1280 * 16384
  assert slab_bytes <= mb["alias"] < 1.001 * slab_bytes, mb
  assert mb["temp"] < 0.7e9, mb
  assert res["device_bytes"] < 13.7e9 < V5E_HBM_BYTES, res["device_bytes"]
  leaf = "bf16[24,16384,640]"
  assert leaf not in res["entry_copies"], res["entry_copies"]
  assert leaf not in res["copies_back_to_hbm"], res["copies_back_to_hbm"]
  assert res["while_loops"] == 1, res
  assert res["tpu_custom_calls"] >= 5 + 5, res["tpu_custom_calls"]
  (dec, _, _, _), = made
  assert dec.attn_reads[4] == (5 * 4, 5 * 4, 0)
  assert dec.cursor_writes[4] == (5 * 4, 5 * 4)


@pytest.mark.parametrize("bucket,temp_max", [
    (2048, 1.4e9),
    # the small chunk runs the same code in less memory: outside tier-1
    pytest.param(256, 0.5e9, marks=pytest.mark.slow)])
def test_deepseek_prefill_chunk_builds_no_score_tensor(topo, monkeypatch,
                                                       bucket, temp_max):
  """The same cell's largest prefill chunk (2048 tokens) and its 256-token
  one into a positional row of 16384 (0.105 GB: five latent leaves; one
  program for a cursor at 0 and above it): the first chunk attends itself and
  a later chunk the row in blocks of 2048, expanded as they are met, through
  the flash FORWARD at 128 heads with keys of 192 / values of 128, so the
  dense branch's float32 scores of chunk x 128 x 16384 (17 GB at 2048 tokens)
  and its expanded row (1.34 GB) do not exist: temporaries stay under 1.4 GB
  (1.14 / 0.32 when written), and the program fits beside the resident slab
  of 24 slots (2.52 GB) under the 13.7 GB ISSUE 40's step zero allows."""
  res = _gate_one("deepseek_prefill_%d" % bucket, monkeypatch)
  mb = res["memory_bytes"]
  assert mb["temp"] < temp_max, mb
  assert res["device_bytes"] + 24 * 5 * 1280 * 16384 < 13.7e9, res
  # the flash kernel a layer (first chunk) and again a layer (later chunks)
  assert res["tpu_custom_calls"] >= 10, res["tpu_custom_calls"]


def test_keye_step_many_selects_and_reads_three_leaves_in_place(
    topo, monkeypatch):
  """The cell keye-vl2-serve-backlog's decode step at its real size (6 layers
  at published widths, 32 / 4 heads of 128, an indexer of 16 heads of 64, 16
  held softmax experts a layer, 16 slots x 32768, horizon 4): ONE slab of
  three leaves a layer (K and V of 16 x 32768 x 512, the index key of 16 x
  32768 x 128: 7.25 GB) is aliased whole; with 1.32 GB of weights beside it
  the program holds 8.7 GB; no leaf is copied at the program's edge or comes
  back from fast memory; all 6 attention reads a step took the kernel that
  stops at the cursor UNDER THE KEEP ROWS (a read fallen to the dense masked
  contraction over all 32768 rows fails HERE and not in a chip run) and all
  18 leaf writes the DMA kernel; all 6 exact selections a step ran their
  threshold search in ``ops.select_topk``'s kernel, so the one while loop
  left is the horizon's scan (each layer's two searches of 16 passes were two
  more, before PR 45)."""
  import tools.mosaic_gate as gate
  from tools.mosaic_gate import V5E_HBM_BYTES
  made, build = [], gate.keye_decoder
  monkeypatch.setattr(gate, "keye_decoder",
                      lambda *a: made.append(build(*a)) or made[-1])
  res = _gate_one("serving_decode_keye", monkeypatch)
  mb = res["memory_bytes"]
  slab_bytes = 16 * 6 * 2304 * 32768
  assert slab_bytes <= mb["alias"] < 1.001 * slab_bytes, mb
  assert mb["temp"] < 0.3e9, mb
  assert res["device_bytes"] < 9.0e9 < 13.7e9 < V5E_HBM_BYTES, res
  for leaf in ("bf16[16,32768,512]", "bf16[16,32768,128]"):
    assert leaf not in res["entry_copies"], res["entry_copies"]
    assert leaf not in res["copies_back_to_hbm"], res["copies_back_to_hbm"]
  # three writes, one selection, one read and three expert products a layer
  assert res["tpu_custom_calls"] == 6 * 8, res["tpu_custom_calls"]
  assert res["while_loops"] == 1, res
  (dec, _, _, _), = made
  assert dec.attn_reads[4] == (6 * 4, 6 * 4, 0)
  assert dec.sparse_reads[4] == 6 * 4
  assert dec.cursor_writes[4] == (18 * 4, 18 * 4)
  assert dec.expert_products["step", 4] == (18 * 4, 18 * 4)
  assert dec.index_selections["step", 4] == (6 * 4, 6 * 4)


@pytest.mark.parametrize("bucket,temp_max", [
    (4096, 1.2e9),
    # the small chunk runs the same code in less memory: outside tier-1
    pytest.param(256, 0.2e9, marks=pytest.mark.slow)])
def test_keye_prefill_chunk_builds_no_score_tensor_of_heads(
    topo, monkeypatch, bucket, temp_max):
  """The same cell's largest prefill chunk (4096 tokens) and its 256-token
  one into a positional row of 32768 (0.453 GB: three leaves a layer; one
  program for a cursor at 0 and above it, selecting or not): the index scores
  are ``[chunk, 32768]`` float32, ONE head's worth after the 16 are summed a
  block of 2048 keys at a time (0.54 GB at 4096 tokens; the 16 heads' would be
  8.6 GB), the selection's keep operand int8, and the attention goes through
  the flash forward and the block call under it, so no ``[chunk, 32, 32768]``
  float32 score tensor (17 GB) exists: temporaries stay under 1.2 GB (0.98 /
  0.06 since the selection's search runs in ``ops.select_topk``'s kernel: the
  XLA search's keys and halves made them 2.53 GB, so a selection fallen back
  to it fails HERE), and the program fits beside the resident slab of 16
  slots (7.25 GB) under the 13.7 GB ISSUE 44's step zero allows."""
  res = _gate_one("keye_prefill_%d" % bucket, monkeypatch)
  mb = res["memory_bytes"]
  assert mb["temp"] < temp_max, mb
  assert res["device_bytes"] + 16 * 6 * 2304 * 32768 < 13.7e9, res
  # the flash kernel a layer (first chunk) and again a layer (later chunks),
  # the selection and the expert products, in the branch that selects at least
  assert res["tpu_custom_calls"] >= 6 * 6, res["tpu_custom_calls"]


@pytest.mark.parametrize("kind", ["decode", "chunk"])
@pytest.mark.parametrize("config", ["trinity", "mimo", "deepseek",
                                    "kimi_linear", "keye"])
def test_expert_product_compiles_at_the_cells_shapes(topo, monkeypatch, config,
                                                     kind):
  """``ops.expert_product`` as a layer calls it (the rows through the gate
  stack, the result's shape through the down stack: K and N exchanged) at the
  five expert cells' widths, for a decode step's rows (96 to 1152, one row
  tile where there are fewer than 128) and for the largest chunk's (8192 to
  16384): the grid whose length is a prefetched scalar, the index maps that
  read the pairs from SMEM and the ``[K, tn]`` blocks (up to 8 MB, double
  buffered) lower through Mosaic; two kernels and no loop (the pairs are a
  few fused reductions); the temporaries are the hidden activations (rows x
  expert width in f32 and again in bf16) and nothing of a stack's shape."""
  from tools.mosaic_gate import EXPERT_PRODUCTS
  held, d, f, step, chunk = EXPERT_PRODUCTS[config]
  rows = step if kind == "decode" else chunk
  res = _gate_one("expert_product_%s_%s" % (config, kind), monkeypatch)
  assert res["tpu_custom_calls"] == 2 and res["while_loops"] == 0, res
  mb = res["memory_bytes"]
  assert mb["temp"] < rows * f * (4 + 2) + (4 << 20), mb
  for stack in ("bf16[%d,%d,%d]" % (held, d, f),
                "bf16[%d,%d,%d]" % (held, f, d)):
    assert stack not in res["entry_copies"], res["entry_copies"]
    assert stack not in res["copies_back_to_hbm"], res["copies_back_to_hbm"]


@pytest.mark.parametrize("kind", ["step", "chunk"])
def test_select_topk_compiles_at_the_cells_shapes(topo, monkeypatch, kind):
  """``ops.select_topk`` at the Keye cell's two shapes, 2048 of a row of 32768
  for a decode step's 16 queries (one tile of 16 rows) and a chunk's 4096
  (tiles of 64): the manual DMAs of a tile's live blocks, the 32 passes over
  the keys in VMEM (29 MB a tile of 64 with its landing buffers and mask) and
  the int8 mask lower through Mosaic; ONE kernel and no loop outside it; beside
  the scores and the mask nothing of the scores' size is left in HBM (the XLA
  search's keys and halves were 2.5 GB of a chunk program's temporaries)."""
  from tools.mosaic_gate import SELECT_TOPK
  rows = SELECT_TOPK[kind]
  res = _gate_one("select_topk_keye_%s" % kind, monkeypatch)
  assert res["tpu_custom_calls"] == 1 and res["while_loops"] == 0, res
  # the int8 mask at most
  assert res["memory_bytes"]["temp"] <= rows * 32768, res


def test_smoke_train_loop_compiles_and_fits(topo, monkeypatch):
  """The whole make_train_loop K-step scan of chip_smoke's train phase
  (abstract state) compiles for one v5e chip, carries the flash and
  LayerNorm kernels, and fits 16 GB by memory_analysis()."""
  from tools.mosaic_gate import V5E_HBM_BYTES
  res = _gate_one("smoke_train_loop", monkeypatch)
  assert res["tpu_custom_calls"] >= 1, res
  assert 0 < res["device_bytes"] < V5E_HBM_BYTES, res
  assert not any(res["collectives"].values()), res["collectives"]


def test_smoke_mesh_train_loop_compiles_sharded(topo, monkeypatch):
  """chip_smoke --chips 4's mesh leg (data=2 x tensor=2, NamedSharding on
  the four described chips): compiles — which needs the flash kernel
  shard_mapped, GSPMD refuses to partition a Mosaic kernel on its own —
  with collectives in, and per-device bytes (3.1 GB when written) well
  under the one-chip program's 9.7 GB."""
  from tools.mosaic_gate import V5E_HBM_BYTES
  res = _gate_one("smoke_mesh_train_loop", monkeypatch)
  assert res["tpu_custom_calls"] >= 1, res
  assert res["collectives"]["all-reduce"] > 0, res["collectives"]
  assert res["device_bytes"] < V5E_HBM_BYTES / 3, res
