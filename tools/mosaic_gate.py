"""Deviceless Mosaic-lowering gate: AOT-compile every production Pallas
kernel — and the full fused multi-chip training step — against a TPU
topology, with NO chip claimed.

Interpret-green kernels can be rejected wholesale by real Mosaic lowering
on first chip contact ("XLA layout ... does not match Mosaic layout", a
block off the sublane tiling, a kernel GSPMD cannot partition), and chip
time is the expensive place to find that out. This gate removes that
dependency: ``jax.jit(...).lower(...).compile()`` against
``jax.experimental.topologies.get_topology_desc("v5e:2x2", "tpu")`` runs
the REAL Mosaic pipeline (mosaic/pallas_call_registration ->
tpu_custom_call -> libtpu's compiler) on this CPU-only host — a kernel
that fails Mosaic lowering or TPU layout assignment fails HERE, at CI
time, with no device. What it cannot check: runtime numerics and perf
(still needs a chip — tools/tpu_validate.py).

Wired into ``make validate`` (the ``mosaic-gate`` target). Results land in
MOSAIC_GATE.json; exit code 1 if any target fails.

Usage:  python tools/mosaic_gate.py                 # full gate
        python tools/mosaic_gate.py --targets flash_gqa_fused_bwd,train_step
        python tools/mosaic_gate.py --list
"""

import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

def _libtpu_init_env():
  """The init identifiers libtpu wants when no metadata server answers.

  Off-GCE the instance-metadata endpoint can refuse (403) rather than
  fail fast, and libtpu's fetch retries each variable 30 times — the
  PJRT plugin init then blocks for minutes inside a C call no signal
  can interrupt (TOS001, observed hanging the whole tier-1 run). These
  must be set before the FIRST topology/backend init in the process, so
  every entry into the plugin (`_topology` and the CLI sanitize) routes
  through here."""
  os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
  os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
  os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")


def _ensure_clean_env():
  """Before jax backend init: the gate compiles FOR a described chip and
  never touches an attached one — the client stays on the CPU, real-kernel
  mode is forced, and libtpu gets its init identifiers."""
  _libtpu_init_env()
  os.environ["TOS_PALLAS_INTERPRET"] = "0"   # the gate exists for Mosaic
  os.environ["JAX_PLATFORMS"] = "cpu"


_TOPO_CACHE = {}


def _topology(name: str):
  from jax.experimental import topologies
  if name not in _TOPO_CACHE:
    _libtpu_init_env()
    _TOPO_CACHE[name] = topologies.get_topology_desc(name, "tpu")
  return _TOPO_CACHE[name]


def _mesh1():
  """A single-device Mesh carved from the 4-chip topology (plain kernels
  need no partitioning semantics; a 1-device mesh pins the lowering to the
  TPU target without tripping 'Mosaic kernels cannot be automatically
  partitioned')."""
  import numpy as np
  from jax.sharding import Mesh
  return Mesh(np.array(_topology("v5e:2x2").devices[:1]), ("one",))


def _repl(mesh):
  from jax.sharding import NamedSharding, PartitionSpec as P
  return NamedSharding(mesh, P())


def _sh(*shape, dtype=None):
  import jax
  import jax.numpy as jnp
  return jax.ShapeDtypeStruct(shape, dtype or jnp.bfloat16)


# --------------------------------------------------------------------------
# Targets. Each returns (jitted_fn, abstract_args); the runner lowers and
# compiles. Shapes mirror the bench/production configs (block tiling is
# shape-dependent, so both the full-tile and clamped-tile paths compile).
# --------------------------------------------------------------------------


def _flash(causal=True, bwd="fused", gqa=False, grad=True, s=1024, d=128,
           window=None):
  import jax
  from tensorflowonspark_tpu.ops.flash_attention import flash_attention
  mesh = _mesh1()
  h, hk = 8, (2 if gqa else 8)
  q, k, v = _sh(1, s, h, d), _sh(1, s, hk, d), _sh(1, s, hk, d)
  if grad:
    def loss(q, k, v):
      return flash_attention(q, k, v, causal=causal, bwd=bwd,
                             window=window).sum()
    fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)),
                 in_shardings=(_repl(mesh),) * 3)
  else:
    fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                                 window=window),
                 in_shardings=(_repl(mesh),) * 3)
  return fn, (q, k, v)


def t_flash_mha_fwd():
  return _flash(grad=False)


def t_flash_mha_fused_bwd():
  return _flash(bwd="fused")


def t_flash_mha_split_bwd():
  return _flash(bwd="split")


def t_flash_gqa_fused_bwd():
  return _flash(bwd="fused", gqa=True)


def t_flash_gqa_split_bwd():
  return _flash(bwd="split", gqa=True)


def t_flash_noncausal_fwd():
  return _flash(causal=False, grad=False)


def t_flash_short_seq_bwd():
  # s < default blocks: the _blocks clamp path (and the post-fallback
  # default re-resolution) must also survive Mosaic
  return _flash(bwd="fused", gqa=True, s=256, d=64)


def t_flash_window_fused_bwd():
  # sliding window (s=4096, W=1024): the windowed loop bounds (traced
  # lo from _window_k_lo / hi from _window_q_hi) must lower — fori_loop
  # with a traced lower bound is a different Mosaic path than 0..hi
  return _flash(bwd="fused", s=4096, window=1024)


def t_flash_window_gqa_split_bwd():
  return _flash(bwd="split", gqa=True, s=4096, window=1024)


def t_ring_attention_window():
  """Windowed ring attention: 4-way sequence mesh at s=8192 with a
  2048-window — ring steps whose KV shard is behind the window collapse
  to zero kernel-loop iterations (the long-context sliding-window
  production path)."""
  import jax
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib
  from tensorflowonspark_tpu.parallel import ring_attention as ra
  from jax.sharding import NamedSharding, PartitionSpec as P
  mesh = mesh_lib.build_mesh(
      mesh_lib.MeshSpec(data=-1, sequence=4),
      devices=list(_topology("v5e:2x2").devices))
  spec = NamedSharding(mesh, P(None, mesh_lib.AXIS_SEQUENCE, None, None))

  def loss(q, k, v):
    return ra.ring_attention(q, k, v, mesh, causal=True, use_flash=True,
                             interpret=False, window=2048).sum()

  fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)),
               in_shardings=(spec, spec, spec))
  return fn, (_sh(1, 8192, 8, 64), _sh(1, 8192, 2, 64),
              _sh(1, 8192, 2, 64))


def t_ring_attention_gqa():
  """The sequence-parallel ring with GQA flash blocks — 4-way sequence
  mesh; grouped KV rotates unexpanded (production long-context path)."""
  import jax
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib
  from tensorflowonspark_tpu.parallel import ring_attention as ra
  from jax.sharding import NamedSharding, PartitionSpec as P
  mesh = mesh_lib.build_mesh(
      mesh_lib.MeshSpec(data=-1, sequence=4),
      devices=list(_topology("v5e:2x2").devices))
  spec = NamedSharding(mesh, P(None, mesh_lib.AXIS_SEQUENCE, None, None))

  def loss(q, k, v):
    return ra.ring_attention(q, k, v, mesh, causal=True,
                             use_flash=True, interpret=False).sum()

  fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)),
               in_shardings=(spec, spec, spec))
  return fn, (_sh(2, 1024, 8, 64), _sh(2, 1024, 2, 64), _sh(2, 1024, 2, 64))


def t_layer_norm():
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu.ops.layer_norm import layer_norm
  mesh = _mesh1()

  def loss(x, w):
    return layer_norm(x, w).astype(jnp.float32).sum()

  fn = jax.jit(jax.grad(loss, argnums=(0, 1)),
               in_shardings=(_repl(mesh),) * 2)
  return fn, (_sh(1024, 1024), _sh(1024, dtype=jnp.float32))


def t_train_step():
  """The FULL fused multi-chip training step — the exact dryrun_multichip(8)
  configuration (ring + GQA-native flash + fused LayerNorm + remat +
  optimizer + collectives) on an 8-chip v5e:2x4
  topology, with the kernels in REAL (non-interpret) mode. The state is
  abstract (eval_shape): nothing ever materializes on a device."""
  import jax
  import jax.numpy as jnp
  from flax.core import meta
  from tensorflowonspark_tpu.models import transformer as tfm
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib
  from tensorflowonspark_tpu.parallel import sharding as sh

  devices = list(_topology("v5e:2x4").devices)
  spec = mesh_lib.MeshSpec(data=-1, fsdp=2, sequence=2, tensor=2)
  mesh = mesh_lib.build_mesh(spec, devices=devices)
  seq_len = 64 * mesh.shape[mesh_lib.AXIS_SEQUENCE]
  cfg = tfm.TransformerConfig(
      vocab_size=512, num_layers=2, num_heads=4, d_model=128, d_ff=256,
      max_seq_len=seq_len, remat=True, use_ring_attention=True,
      layer_norm_impl="fused", attention_impl="flash", num_kv_heads=2)

  params_init, make_state = tfm._init_fns(
      jax.random.PRNGKey(0), cfg, mesh, 3e-4, seq_len,
      init_batch=mesh_lib.axis_size(mesh, mesh_lib.AXIS_DATA,
                                    mesh_lib.AXIS_FSDP))
  abs_boxed = jax.eval_shape(params_init)
  param_sharding = sh.param_sharding_from_boxed(abs_boxed, mesh)
  abs_state = jax.eval_shape(lambda: make_state(meta.unbox(params_init())))
  state_sharding = sh.state_shardings(abs_state, param_sharding, mesh)

  def loss_fn(params, tokens):
    logits = abs_state.apply_fn({"params": params}, tokens)
    return tfm.causal_lm_loss(logits, tokens)

  step = sh.make_train_step(loss_fn, mesh, state_sharding,
                            batch_extra_axes=(mesh_lib.AXIS_SEQUENCE,))
  batch = mesh_lib.axis_size(mesh, mesh_lib.AXIS_DATA,
                             mesh_lib.AXIS_FSDP) * 2
  tokens = jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)
  return step, (abs_state, tokens)


def t_serving_decode():
  """Tensor-parallel KV-cache decode (heads + cache over `tensor`, batch
  over `data`) — the multi-chip serving path, compiled with abstract
  params and an abstract PRNG key (nothing materializes)."""
  import jax
  import jax.numpy as jnp
  from flax.core import meta
  from tensorflowonspark_tpu.models import transformer as tfm
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib
  mesh = mesh_lib.build_mesh(
      mesh_lib.MeshSpec(data=-1, tensor=2),
      devices=list(_topology("v5e:2x2").devices))
  cfg = tfm.TransformerConfig(
      vocab_size=256, num_layers=2, num_heads=4, num_kv_heads=2,
      d_model=128, d_ff=256, max_seq_len=64, remat=False)
  fn = tfm._kv_generate_fn(cfg, 4, 16, 8, 0.0, 0, mesh)
  fn = getattr(fn, "jitted", fn)   # the mesh path wraps jit in device_put
  model = tfm.Transformer(cfg, mesh=mesh)
  abs_params = jax.eval_shape(lambda: meta.unbox(model.init(
      jax.random.PRNGKey(0), jnp.zeros((4, 1), jnp.int32),
      decode=True)["params"]))
  key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
  return fn, (abs_params, jax.ShapeDtypeStruct((4, 16), jnp.int32), key)


def t_pipeline_1f1b():
  """The 1F1B schedule with scattered-input conveyors (4 stages, n_micro=8
  → the ppermute token/target conveyors are engaged) through the real TPU
  compiler — loop + collective lowering, no Pallas."""
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib
  from tensorflowonspark_tpu.parallel import pipeline_parallel as pp
  mesh = mesh_lib.build_mesh(
      mesh_lib.MeshSpec(pipeline=4),
      devices=list(_topology("v5e:2x2").devices))

  def step(W, x, t):
    return pp.pipeline_train_step(
        lambda w, a: jnp.tanh(a @ w),
        lambda y, tg: jnp.mean((y - tg) ** 2),
        W, x, t, mesh, num_microbatches=8)

  fn = jax.jit(step, in_shardings=(_repl(mesh),) * 3)
  d = 128
  return fn, (_sh(4, d, d, dtype=jnp.float32),
              _sh(32, d, dtype=jnp.float32),
              _sh(32, d, dtype=jnp.float32))


def t_pipeline_lm_flash():
  """The FULL transformer through the 1F1B pipe with flash attention
  forced inside the pipelined stages: Pallas kernels inside a fori_loop
  inside shard_map lax.cond — the hardest lowering composition in the
  repo, previously exercised only in CPU interpret mode."""
  import jax
  import jax.numpy as jnp
  from flax.core import meta
  from tensorflowonspark_tpu.models import transformer as tfm
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib
  mesh = mesh_lib.build_mesh(
      mesh_lib.MeshSpec(pipeline=2),
      devices=list(_topology("v5e:2x2").devices)[:2])
  cfg = tfm.TransformerConfig(
      vocab_size=256, num_layers=2, num_heads=4, num_kv_heads=2,
      d_model=128, d_ff=256, max_seq_len=128, remat=False,
      attention_impl="flash", dtype=jnp.float32)
  model = tfm.Transformer(cfg)
  abs_params = jax.eval_shape(lambda: meta.unbox(model.init(
      jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32))["params"]))
  lm_step = tfm.make_pipeline_train_step(cfg, mesh, num_microbatches=4)
  fn = jax.jit(lm_step)
  return fn, (abs_params, _sh(8, 128, dtype=jnp.int32))


def t_expert_a2a():
  """MoE all-to-all dispatch (top-k gating, capacity drop/combine) over a
  data×expert mesh through the TPU compiler."""
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu.parallel import expert_parallel as ep
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib
  mesh = mesh_lib.build_mesh(
      mesh_lib.MeshSpec(data=2, expert=2),
      devices=list(_topology("v5e:2x2").devices))
  params = jax.eval_shape(
      lambda: ep.init_moe_params(jax.random.PRNGKey(0), 4, 128, 512))

  def step(p, x):
    out = ep.moe_ffn_a2a(p, x, mesh, capacity_factor=2.0, top_k=2)
    return out.sum()

  fn = jax.jit(jax.grad(step, argnums=0))
  return fn, (params, _sh(64, 128, dtype=jnp.float32))


def t_train_step_pod():
  """The fused training step at POD scale: a 32-chip v5e:4x8 topology —
  8 HOSTS (2x2 chips each), so the data axis crosses DCN while
  fsdp/sequence/tensor ride ICI. The virtual-CPU dryrun can never check
  this; the deviceless topology compile proves the multi-host program
  (collectives, ring, Pallas kernels) lowers for real pod shapes."""
  import jax
  import jax.numpy as jnp
  from flax.core import meta
  from tensorflowonspark_tpu.models import transformer as tfm
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib
  from tensorflowonspark_tpu.parallel import sharding as sh

  devices = list(_topology("v5e:4x8").devices)
  assert len(devices) == 32, len(devices)
  spec = mesh_lib.MeshSpec(data=-1, fsdp=2, sequence=2, tensor=2)
  mesh = mesh_lib.build_mesh(spec, devices=devices)
  seq_len = 128 * mesh.shape[mesh_lib.AXIS_SEQUENCE]
  cfg = tfm.TransformerConfig(
      vocab_size=1024, num_layers=2, num_heads=8, d_model=256, d_ff=512,
      max_seq_len=seq_len, remat=True, use_ring_attention=True,
      layer_norm_impl="fused", attention_impl="flash", num_kv_heads=2)

  params_init, make_state = tfm._init_fns(
      jax.random.PRNGKey(0), cfg, mesh, 3e-4, seq_len,
      init_batch=mesh_lib.axis_size(mesh, mesh_lib.AXIS_DATA,
                                    mesh_lib.AXIS_FSDP))
  abs_boxed = jax.eval_shape(params_init)
  param_sharding = sh.param_sharding_from_boxed(abs_boxed, mesh)
  abs_state = jax.eval_shape(lambda: make_state(meta.unbox(params_init())))
  state_sharding = sh.state_shardings(abs_state, param_sharding, mesh)

  def loss_fn(params, tokens):
    logits = abs_state.apply_fn({"params": params}, tokens)
    return tfm.causal_lm_loss(logits, tokens)

  step = sh.make_train_step(loss_fn, mesh, state_sharding,
                            batch_extra_axes=(mesh_lib.AXIS_SEQUENCE,))
  batch = mesh_lib.axis_size(mesh, mesh_lib.AXIS_DATA,
                             mesh_lib.AXIS_FSDP) * 2
  tokens = jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)
  return step, (abs_state, tokens)


def t_ring_attention_pod():
  """16-way ring attention on a 16-chip v5e:4x4 (4-host) topology — the
  long-context scaling claim compiled at a real pod shape."""
  import jax
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib
  from tensorflowonspark_tpu.parallel import ring_attention as ra
  from jax.sharding import NamedSharding, PartitionSpec as P
  mesh = mesh_lib.build_mesh(
      mesh_lib.MeshSpec(data=-1, sequence=16),
      devices=list(_topology("v5e:4x4").devices))
  spec = NamedSharding(mesh, P(None, mesh_lib.AXIS_SEQUENCE, None, None))

  def loss(q, k, v):
    return ra.ring_attention(q, k, v, mesh, causal=True,
                             use_flash=True, interpret=False).sum()

  fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)),
               in_shardings=(spec, spec, spec))
  return fn, (_sh(1, 8192, 8, 128), _sh(1, 8192, 2, 128),
              _sh(1, 8192, 2, 128))


def t_serving_decode_int8():
  """Tensor-parallel decode with the int8 KV cache (quantize on write,
  dequant fused into the einsum reads) — the serving-memory lever
  compiled for TPU."""
  import jax
  import jax.numpy as jnp
  from flax.core import meta
  from tensorflowonspark_tpu.models import transformer as tfm
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib
  mesh = mesh_lib.build_mesh(
      mesh_lib.MeshSpec(data=-1, tensor=2),
      devices=list(_topology("v5e:2x2").devices))
  cfg = tfm.TransformerConfig(
      vocab_size=256, num_layers=2, num_heads=4, num_kv_heads=2,
      d_model=128, d_ff=256, max_seq_len=64, remat=False,
      kv_cache_dtype="int8")
  fn = tfm._kv_generate_fn(cfg, 4, 16, 8, 0.0, 0, mesh)
  fn = getattr(fn, "jitted", fn)
  model = tfm.Transformer(cfg, mesh=mesh)
  abs_params = jax.eval_shape(lambda: meta.unbox(model.init(
      jax.random.PRNGKey(0), jnp.zeros((4, 1), jnp.int32),
      decode=True)["params"]))
  key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
  return fn, (abs_params, jax.ShapeDtypeStruct((4, 16), jnp.int32), key)


def t_serving_speculative():
  """Greedy speculative decode — draft scan + batched target verify +
  cursor-rewind rollback inside a while_loop, two KV caches in the
  carry — compiled for TPU on one topology device."""
  import jax
  import jax.numpy as jnp
  from flax.core import meta
  from tensorflowonspark_tpu.models import transformer as tfm
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib
  mesh = mesh_lib.build_mesh(
      mesh_lib.MeshSpec(data=1),
      devices=list(_topology("v5e:2x2").devices)[:1])
  base = dict(vocab_size=256, num_heads=4, num_kv_heads=2, d_model=128,
              d_ff=256, max_seq_len=64, remat=False)
  cfg = tfm.TransformerConfig(num_layers=2, **base)
  dcfg = tfm.TransformerConfig(num_layers=1, **base)
  fn = tfm._spec_generate_fn(dcfg, cfg, 2, 16, 16, 4, mesh)

  def abs_params(c):
    return jax.eval_shape(lambda: meta.unbox(tfm.Transformer(c).init(
        jax.random.PRNGKey(0), jnp.zeros((2, 1), jnp.int32),
        decode=True)["params"]))

  return fn, (abs_params(dcfg), abs_params(cfg),
              jax.ShapeDtypeStruct((2, 16), jnp.int32))


def t_serving_prefill_flash():
  """Tensor-parallel serving with a 128-token prompt: the fresh-cache
  prefill runs through the GQA flash kernel shard_mapped over the
  data×tensor mesh, inside the decode program's lax.cond (dense fallback
  branch compiled alongside)."""
  import jax
  import jax.numpy as jnp
  from flax.core import meta
  from tensorflowonspark_tpu.models import transformer as tfm
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib
  mesh = mesh_lib.build_mesh(
      mesh_lib.MeshSpec(data=-1, tensor=2),
      devices=list(_topology("v5e:2x2").devices))
  cfg = tfm.TransformerConfig(
      vocab_size=256, num_layers=2, num_heads=4, num_kv_heads=2,
      d_model=128, d_ff=256, max_seq_len=192, remat=False,
      attention_impl="flash")
  fn = tfm._kv_generate_fn(cfg, 4, 128, 8, 0.0, 0, mesh)
  fn = getattr(fn, "jitted", fn)
  model = tfm.Transformer(cfg, mesh=mesh)
  abs_params = jax.eval_shape(lambda: meta.unbox(model.init(
      jax.random.PRNGKey(0), jnp.zeros((4, 1), jnp.int32),
      decode=True)["params"]))
  key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
  return fn, (abs_params, jax.ShapeDtypeStruct((4, 128), jnp.int32), key)


def t_pipeline_gpipe():
  """The GPipe fill-drain forward (grad through whole-loop AD) — the
  other pipeline schedule, compiled for TPU."""
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib
  from tensorflowonspark_tpu.parallel import pipeline_parallel as pp
  mesh = mesh_lib.build_mesh(
      mesh_lib.MeshSpec(pipeline=4),
      devices=list(_topology("v5e:2x2").devices))

  def loss(W, x):
    return pp.pipeline_apply(lambda w, a: jnp.tanh(a @ w), W, x, mesh,
                             num_microbatches=4).sum()

  fn = jax.jit(jax.grad(loss, argnums=(0,)), in_shardings=(_repl(mesh),) * 2)
  d = 128
  return fn, (_sh(4, d, d, dtype=jnp.float32), _sh(16, d, dtype=jnp.float32))


def t_resnet_bench():
  """The image model's train step (ResNet-50 at batch 128 / 224x224)
  compiled against the 1-device topology: proves the conv stack lowers.
  (A deviceless compile cannot be read back from the persistent cache on a
  chip, so this warms nothing.)"""
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import resnet
  mesh = _mesh1()
  repl = _repl(mesh)
  model = resnet.ResNet50(num_classes=1000)
  abs_state = jax.eval_shape(
      lambda: resnet.create_state(jax.random.PRNGKey(0), model,
                                  image_shape=(224, 224, 3)))
  fn = jax.jit(resnet.train_step, in_shardings=(repl, repl, repl),
               out_shardings=repl)
  images = jax.ShapeDtypeStruct((128, 224, 224, 3), jnp.float32)
  labels = jax.ShapeDtypeStruct((128,), jnp.int32)
  return fn, (abs_state, images, labels)


# --------------------------------------------------------------------------
# chip_smoke.py's programs at its REAL shapes (the 12-layer / 768 / 12x64 /
# 3072 / vocab-32000 model at 16 x 1024): what the first chip run would
# otherwise spend its budget discovering. Args carry the described device as
# their sharding, since the jits under test place nothing themselves.
# --------------------------------------------------------------------------


def _smoke_conf():
  import chip_smoke
  return dict(chip_smoke.FULL, seed=0, rehearse=False)


def _on_chip0(tree):
  """Abstract pytree pinned to the first described chip."""
  import jax
  from jax.sharding import SingleDeviceSharding
  one = SingleDeviceSharding(_topology("v5e:2x2").devices[0])
  return jax.tree.map(
      lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree)


def _smoke_flash(grad: bool):
  import jax
  from tensorflowonspark_tpu.ops.flash_attention import flash_attention
  conf = _smoke_conf()
  w = conf["widths"]
  h = w["num_heads"]
  q = _on_chip0(_sh(conf["batch"], conf["seq"], h, w["d_model"] // h))
  if grad:
    fn = jax.jit(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        bwd="fused").sum(),
        argnums=(0, 1, 2)))
  else:
    fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
  return fn, (q, q, q)


def t_smoke_flash_fwd():
  """Flash forward at B16 S1024 H12 D64 bf16 (head_dim 64, not the 128 the
  older targets use)."""
  return _smoke_flash(grad=False)


def t_smoke_flash_fused_bwd():
  return _smoke_flash(grad=True)


def t_smoke_layer_norm():
  """Fused LayerNorm forward+backward at 16384 x 768."""
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu.ops.layer_norm import layer_norm
  conf = _smoke_conf()
  d = conf["widths"]["d_model"]
  x = _on_chip0(_sh(conf["batch"] * conf["seq"], d))
  g = _on_chip0(_sh(d, dtype=jnp.float32))
  fn = jax.jit(jax.grad(
      lambda x, g: layer_norm(x, g).astype(jnp.float32).sum(),
      argnums=(0, 1)))
  return fn, (x, g)


def t_smoke_train_loop():
  """The whole make_train_loop K-step scan of chip_smoke's train phase,
  abstract state (jax.eval_shape), one described chip."""
  import jax
  import jax.numpy as jnp
  import numpy as np
  import chip_smoke
  from jax.sharding import Mesh
  from tensorflowonspark_tpu.data.readers import Slab
  from tensorflowonspark_tpu.models import transformer as tfm
  from tensorflowonspark_tpu.parallel import sharding as sh
  conf = _smoke_conf()
  B, S, K = conf["batch"], conf["seq"], conf["unroll"]
  cfg = chip_smoke.model_config(conf, S)
  abs_state = _on_chip0(jax.eval_shape(
      lambda: tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=S)))
  mesh = Mesh(np.array(_topology("v5e:2x2").devices[:1]), ("data",))
  loop = sh.make_train_loop(chip_smoke.lm_loss_fn(cfg), mesh, unroll=K)
  slab = Slab(_on_chip0(_sh(K, B, S, dtype=jnp.int32)))
  return loop, (abs_state, slab)


def t_smoke_mesh_train_loop():
  """chip_smoke --chips 4's mesh leg: create_sharded_state's layouts +
  make_train_loop on data=2 x tensor=2 over the four described chips."""
  import jax
  import jax.numpy as jnp
  import chip_smoke
  from flax.core import meta
  from tensorflowonspark_tpu.data.readers import Slab
  from tensorflowonspark_tpu.models import transformer as tfm
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib
  from tensorflowonspark_tpu.parallel import sharding as sh
  conf = _smoke_conf()
  B, S, K = conf["batch"], conf["seq"], conf["unroll"]
  mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=2, tensor=2),
                             devices=list(_topology("v5e:2x2").devices))
  cfg = chip_smoke.model_config(conf, S)
  params_init, make_state = tfm._init_fns(
      jax.random.PRNGKey(0), cfg, mesh, 3e-4, S, init_batch=2)
  param_sharding = sh.param_sharding_from_boxed(
      jax.eval_shape(params_init), mesh)
  abs_state = jax.eval_shape(lambda: make_state(meta.unbox(params_init())))
  sharding = sh.state_shardings(abs_state, param_sharding, mesh)
  loop = sh.make_train_loop(chip_smoke.lm_loss_fn(cfg, mesh), mesh, sharding,
                            unroll=K)
  slab = Slab(jax.ShapeDtypeStruct((K, B, S), jnp.int32))
  return loop, (abs_state, slab)


def _smoke_decoder(paged: bool):
  """(SlotDecoder, abstract params, row cache, slab) at the serve phase's
  widths; contiguous is the engine's default layout, paged the other."""
  import jax
  import jax.numpy as jnp
  import chip_smoke
  from flax.core import meta
  from tensorflowonspark_tpu.models import transformer as tfm
  from tensorflowonspark_tpu.serving import engine as engine_mod
  from tensorflowonspark_tpu.serving import slots as slots_lib
  conf = _smoke_conf()
  cfg = chip_smoke.model_config(conf, conf["serve_max_seq"])
  dec = slots_lib.SlotDecoder(cfg, engine_mod._DEFAULT_SLOTS,
                              page_size=16 if paged else 0)
  params = _on_chip0(jax.eval_shape(lambda: meta.unbox(dec.model.init(
      jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])))
  row = _on_chip0(jax.eval_shape(lambda: tfm._zero_cache(dec.model, 1)))
  slabs = _on_chip0(jax.eval_shape(dec.init_slabs))
  return dec, params, row, slabs


def _i32(*shape):
  import jax.numpy as jnp
  return _on_chip0(_sh(*shape, dtype=jnp.int32))


#: serving.slots.DEFAULT_BUCKETS, spelled out so listing the targets
#: imports nothing; smoke_prefill checks the two stay equal
SMOKE_BUCKETS = (512, 256, 128, 64, 32, 16)


def smoke_prefill(bucket: int):
  """One prefill chunk program of the padded plan (the chunk's true length
  is a traced scalar; fresh-cache flash branch and warm-cache dense branch
  live in the same cond)."""
  from tensorflowonspark_tpu.serving.slots import DEFAULT_BUCKETS
  assert tuple(DEFAULT_BUCKETS) == SMOKE_BUCKETS, DEFAULT_BUCKETS
  dec, params, row, _ = _smoke_decoder(paged=False)
  assert dec.padded_prefill
  return dec._prefill_fn, (params, row, _i32(1, bucket), _i32())


def t_smoke_insert():
  dec, _, row, slabs = _smoke_decoder(paged=False)
  return dec._insert_fn, (slabs, row, _i32())


def _step_many_target(dec, params, slabs):
  """``SlotDecoder.step_many``'s program at the engine's default horizon."""
  import jax.numpy as jnp
  from tensorflowonspark_tpu.serving import engine as engine_mod
  n = dec.num_slots
  return dec.step_many_jit(engine_mod._DEFAULT_HORIZON), (
      params, slabs, _i32(n), _on_chip0(_sh(n, dtype=jnp.bool_)), _i32(n))


def _smoke_step_many(paged: bool):
  dec, params, _, slabs = _smoke_decoder(paged)
  return _step_many_target(dec, params, slabs)


def _gpt2l_decoder():
  """(SlotDecoder, abstract bf16 params) of the benchmark's serving cells:
  gpt2-large (36 x 1280 x 20 heads of 64, vocab 50257), 16 slots x 1024."""
  import jax
  import jax.numpy as jnp
  from flax.core import meta
  from tensorflowonspark_tpu.models import transformer as tfm
  from tensorflowonspark_tpu.serving import slots as slots_lib
  cfg = tfm.TransformerConfig(
      vocab_size=50257, num_layers=36, num_heads=20, d_model=1280,
      d_ff=5120, max_seq_len=1024, remat=False)
  dec = slots_lib.SlotDecoder(cfg, 16)
  params = _on_chip0(jax.eval_shape(lambda: jax.tree.map(
      lambda x: x.astype(jnp.bfloat16) if x.ndim > 1 else x,
      meta.unbox(dec.model.init(
          jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))))
  return dec, params


def t_gpt2l_step_many():
  """The benchmark's serving step at its real size, horizon 4 — the one
  size at which the compiler's fast-memory staging of a 3 GB slab shows
  (PERF.md section 6, PR 25)."""
  import jax
  dec, params = _gpt2l_decoder()
  return _step_many_target(dec, params,
                           _on_chip0(jax.eval_shape(dec.init_slabs)))


def t_cursor_write():
  """``ops.cursor_write`` alone, on K and V of one layer of the benchmark's
  GPT-2 slab (16 slots x 1024 x 1280, bf16), both donated as the serving
  step donates its slab. (A donating program that is ONE such call and
  returns its bare result does not compile: "Different aliasing shapes",
  the HBM-pinned result against the entry's own layout; any program that
  returns a tuple does.)"""
  import jax
  from tensorflowonspark_tpu.ops.cursor_write import cursor_write

  def layer(k, v, new_k, new_v, idx):
    return cursor_write(k, new_k, idx), cursor_write(v, new_v, idx)

  leaf, new = _on_chip0(_sh(16, 1024, 1280)), _on_chip0(_sh(16, 1280))
  return jax.jit(layer, donate_argnums=(0, 1)), (leaf, leaf, new, new,
                                                 _i32(16))


def _decode_attention_target(slots: int, max_seq: int, heads: int, d: int):
  """``ops.decode_attention`` and ``ops.cursor_write`` on K and V of ONE
  layer of a serving slab, donated as the serving step donates its slab:
  the read takes the leaves as they were, the write is in place."""
  import jax
  from tensorflowonspark_tpu import ops

  def layer(k, v, q, new_k, new_v, idx):
    o = ops.decode_attention(q, new_k, new_v, k, v, idx)
    flat = lambda x: x.reshape(slots, heads * d)  # noqa: E731
    return (ops.cursor_write(k, flat(new_k), idx),
            ops.cursor_write(v, flat(new_v), idx), o)

  leaf = _on_chip0(_sh(slots, max_seq, heads * d))
  new = _on_chip0(_sh(slots, heads, d))
  return jax.jit(layer, donate_argnums=(0, 1)), (leaf, leaf, new, new, new,
                                                 _i32(slots))


def t_decode_attention():
  """The decode step's attention kernel beside the cursor write at the
  GPT-2 cells' widths: 16 slots x 1024 x 20 heads of 64 (a head is half a
  vreg's lanes: the output leaves folded onto 128)."""
  return _decode_attention_target(16, 1024, 20, 64)


def t_decode_attention_ouro():
  """The same at the Ouro cell's widths: 8 slots x 512 x 16 heads of 128."""
  return _decode_attention_target(OURO_SLOTS, OURO_MAX_SEQ, 16, 128)



#: the grouped expert product of the four expert cells: (held experts, model
#: width, expert width, assignment rows of a decode step, of the largest
#: prefill chunk). Kimi's float32 activations go in as three bf16 terms a
#: row: 3 x (48 slots x 8, 512 tokens x 8)
EXPERT_PRODUCTS = {
    "trinity": (32, 3072, 3072, 24 * 4, 2048 * 4),
    "mimo": (16, 4096, 2048, 48 * 8, 2048 * 8),
    "deepseek": (16, 7168, 2048, 24 * 8, 2048 * 8),
    "keye": (16, 2048, 768, 16 * 8, 4096 * 8),
    "kimi_linear": (16, 2304, 1024, 3 * 48 * 8, 3 * 512 * 8),
}


def expert_product_target(held: int, d: int, f: int, rows: int):
  """``ops.expert_product`` as a layer's feed-forward calls it: ``rows``
  assignment rows through the gate stack (``[held, d, f]``) and its result's
  shape through the down stack (``[held, f, d]``)."""
  import jax
  from tensorflowonspark_tpu import ops

  def layer(x, gate, down, sizes):
    hidden = ops.expert_product(x, gate, sizes)
    return ops.expert_product(hidden.astype(x.dtype), down, sizes)

  return jax.jit(layer), (_on_chip0(_sh(rows, d)), _on_chip0(_sh(held, d, f)),
                          _on_chip0(_sh(held, f, d)), _i32(held))


#: ``ops.select_topk`` at the Keye cell's two shapes: a decode step's 16 slots
#: and a 4096-token chunk, over a row of 32768 positions, 2048 kept
SELECT_TOPK = {"step": 16, "chunk": 4096}


def select_topk_target(rows: int, n: int = 32768, k: int = 2048):
  """``ops.select_topk`` alone: ``rows`` queries' float32 index scores over
  ``n`` positions and each query's last candidate."""
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu import ops

  return (jax.jit(lambda scores, last: ops.select_topk(scores, last, k)),
          (_on_chip0(_sh(rows, n, dtype=jnp.float32)), _i32(rows)))


def t_gpt2l_prefill_512():
  """The benchmark's largest prefill program at its real size: a padded
  512-token chunk (PERF.md section 6, PR 27); only the last real row may
  reach the 50257-wide head."""
  import jax
  from tensorflowonspark_tpu.models import transformer as tfm
  dec, params = _gpt2l_decoder()
  row = _on_chip0(jax.eval_shape(lambda: tfm._zero_cache(dec.model, 1)))
  return dec._prefill_fn, (params, row, _i32(1, 512), _i32())


#: the benchmark cell kimi-linear-serve-backlog: slots x max_seq
KIMI_LINEAR_SLOTS, KIMI_LINEAR_MAX_SEQ = 48, 4096


def kimi_linear_cfg(max_seq: int = KIMI_LINEAR_MAX_SEQ):
  """Kimi-Linear-48B-A3B-Instruct as ``benchmarks/configs/
  kimi-linear-48b-a3b.json`` cuts it to one chip's share (published widths,
  all 27 layers, 16 of 256 experts held, 1/8 of the vocabulary), spelled
  out so that the gate needs nothing of ``benchmarks/``;
  ``benchmarks/tests/test_kimi_linear.py`` keeps the two equal."""
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import transformer as tfm
  return tfm.TransformerConfig(
      vocab_size=20480, num_layers=27, num_heads=32, d_model=2304, d_ff=9216,
      max_seq_len=max_seq, remat=False, dtype=jnp.bfloat16,
      layer_types=tuple("mla" if i in (3, 7, 11, 15, 19, 23, 26) else "kda"
                        for i in range(27)),
      ffn_types=("mlp",) + ("experts",) * 26, norm="rms", norm_eps=1e-5,
      mlp_act="swiglu", tie_embeddings=False, kda_heads=32, kda_head_dim=128,
      kda_conv=4, kda_rank=128, mla_kv_rank=512, mla_nope_dim=128,
      mla_rope_dim=64, mla_v_dim=128, experts_total=256, experts_held=16,
      experts_first=0, experts_top_k=8, experts_d_ff=1024, experts_shared=1,
      experts_scale=2.446, act_f32=True)


def kimi_linear_decoder(slots: int = KIMI_LINEAR_SLOTS,
                        max_seq: int = KIMI_LINEAR_MAX_SEQ):
  """(SlotDecoder, abstract bf16 params, row cache, slab) at the cell's
  sizes."""
  import jax
  import jax.numpy as jnp
  from flax.core import meta
  from tensorflowonspark_tpu.models import transformer as tfm
  from tensorflowonspark_tpu.serving import slots as slots_lib
  dec = slots_lib.SlotDecoder(kimi_linear_cfg(max_seq), slots)
  f32 = ("scale", "A_log", "dt_bias", "o_norm", "router", "router_bias")
  params = _on_chip0(jax.eval_shape(lambda: jax.tree_util.tree_map_with_path(
      lambda p, x: x if p[-1].key in f32 else x.astype(jnp.bfloat16),
      meta.unbox(dec.model.init(
          jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))))
  row = _on_chip0(jax.eval_shape(lambda: tfm._zero_cache(dec.model, 1)))
  return dec, params, row, _on_chip0(jax.eval_shape(dec.init_slabs))


def t_serving_decode_kimi_linear():
  """The cell kimi-linear-serve-backlog's decode step at its real size: 27
  layers at published widths (20 KDA states of 100 MB and 7 latent caches
  of 226 MB in one slab of 48 x 4096), horizon 4."""
  dec, params, _, slabs = kimi_linear_decoder()
  return _step_many_target(dec, params, slabs)


#: the two largest shapes of that cell's prefill ladder
#: (``serving.slots.row_buckets(4096)``; kimi_linear_prefill checks it): its
#: mix's twelve prompt lengths run 1.30 chunks of 512 and 0.28 of 256 a prompt
KIMI_LINEAR_BUCKETS = (512, 256)


def kimi_linear_prefill(bucket: int, padded: bool = True):
  """One of the same cell's largest prefill programs: a chunk of ``bucket``
  tokens of which a traced ``n_valid`` are real (the KDA layers mask the
  rest out of their state and take their convolution tail at the true
  length; the cursor masks the latent cache), into a row of 20 float32
  states and tails and 7 latent leaves of 4096 rows. ``padded=False`` is the
  exact plan's program of the same shape (no ``n_valid``: the [512, vocab]
  logits block, no mask), which the cell ran until PR 37."""
  dec, params, row, _ = kimi_linear_decoder()
  assert dec.padded_prefill
  assert dec.buckets[:len(KIMI_LINEAR_BUCKETS)] == KIMI_LINEAR_BUCKETS, \
      dec.buckets
  return dec._prefill_fn, (params, row, _i32(1, bucket),
                           _i32() if padded else None)


#: the benchmark cell ouro-serve-backlog: slots x max_seq
OURO_SLOTS, OURO_MAX_SEQ = 8, 512


def ouro_cfg(max_seq: int = OURO_MAX_SEQ, layers: int = 48):
  """Ouro-2.6B as ``benchmarks/configs/ouro-2.6b.json`` has it (published
  widths, all 48 layers, 4 passes over them, whole vocabulary), spelled out
  so that the gate needs nothing of ``benchmarks/``;
  ``benchmarks/tests/test_ouro.py`` keeps the two equal."""
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import transformer as tfm
  return tfm.TransformerConfig(
      vocab_size=49152, num_layers=layers, num_heads=16, d_model=2048,
      d_ff=5632,
      max_seq_len=max_seq, remat=False, dtype=jnp.bfloat16, norm="rms",
      norm_eps=1e-6, mlp_act="swiglu", tie_embeddings=False,
      attn_head_dim=128, rope_theta=1e6, post_norm=True, loop_passes=4,
      loop_exit_threshold=1.0)


def ouro_decoder(slots: int = OURO_SLOTS, max_seq: int = OURO_MAX_SEQ,
                 layers: int = 48):
  """(SlotDecoder, abstract params, row cache, slab) at the cell's sizes:
  bf16 matrices, float32 norm scales and exit gate."""
  import jax
  import jax.numpy as jnp
  from flax.core import meta
  from tensorflowonspark_tpu.models import transformer as tfm
  from tensorflowonspark_tpu.serving import slots as slots_lib
  dec = slots_lib.SlotDecoder(ouro_cfg(max_seq, layers), slots)
  params = _on_chip0(jax.eval_shape(lambda: jax.tree_util.tree_map_with_path(
      lambda p, x: x if p[-1].key == "scale" or p[0].key == "exit_gate"
      else x.astype(jnp.bfloat16),
      meta.unbox(dec.model.init(
          jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))))
  row = _on_chip0(jax.eval_shape(lambda: tfm._zero_cache(dec.model, 1)))
  return dec, params, row, _on_chip0(jax.eval_shape(dec.init_slabs))


def t_serving_decode_ouro():
  """The cell ouro-serve-backlog's decode step at its real size: 48 layers
  run 4 times a token over shared weights, 384 K/V leaves of 8 x 512 x 2048
  in the one slab, horizon 4."""
  dec, params, _, slabs = ouro_decoder()
  return _step_many_target(dec, params, slabs)


def t_serving_decode_ouro_4_layers():
  """The same step with 4 of the 48 layers (32 of the 384 leaves), every
  width and the 4 passes as published: what tier-1 compiles (the whole
  program takes two and a half minutes)."""
  dec, params, _, slabs = ouro_decoder(layers=4)
  return _step_many_target(dec, params, slabs)


def t_ouro_prefill_512():
  """The same cell's largest prefill program: a padded 512-token chunk
  through 192 layer applications into a row cache of 384 leaves."""
  dec, params, row, _ = ouro_decoder()
  return dec._prefill_fn, (params, row, _i32(1, 512), _i32())


#: the benchmark cell trinity-serve-backlog: slots x max_seq
TRINITY_SLOTS, TRINITY_MAX_SEQ = 24, 16384


def trinity_cfg(max_seq: int = TRINITY_MAX_SEQ):
  """Trinity-Large-Preview as ``benchmarks/configs/
  trinity-large-preview.json`` cuts it to one chip's share (published
  widths, published layers 6-10: 1 dense + 4 expert layers, sliding,
  sliding, full, sliding, sliding; 32 of 256 experts held, 1/8 of the
  vocabulary), spelled out so that the gate needs nothing of
  ``benchmarks/``; ``benchmarks/tests/test_trinity.py`` keeps the two
  equal."""
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import transformer as tfm
  sliding = (True, True, False, True, True)
  return tfm.TransformerConfig(
      vocab_size=25024, num_layers=5, num_heads=48, num_kv_heads=8,
      attn_head_dim=128, d_model=3072, d_ff=12288, max_seq_len=max_seq,
      remat=False, dtype=jnp.bfloat16,
      ffn_types=("mlp",) + ("experts",) * 4,
      layer_windows=tuple(4096 if s else 0 for s in sliding),
      layer_rope=sliding, qk_norm=True, attn_gate=True,
      embed_scale=3072 ** 0.5, rope_theta=10000.0, post_norm=True,
      norm="rms", norm_eps=1e-5, mlp_act="swiglu", tie_embeddings=False,
      experts_total=256, experts_held=32, experts_first=0, experts_top_k=4,
      experts_d_ff=3072, experts_shared=1, experts_scale=2.448)


def _sparse_decoder(cfg, slots: int):
  """(SlotDecoder, abstract params, row cache, slab) of a model with held
  experts at a cell's sizes: bf16 matrices, float32 norm scales, router,
  router bias and sinks."""
  import jax
  import jax.numpy as jnp
  from flax.core import meta
  from tensorflowonspark_tpu.models import transformer as tfm
  from tensorflowonspark_tpu.serving import slots as slots_lib
  dec = slots_lib.SlotDecoder(cfg, slots)
  f32 = ("scale", "router", "router_bias", "sink")
  params = _on_chip0(jax.eval_shape(lambda: jax.tree_util.tree_map_with_path(
      lambda p, x: x if p[-1].key in f32 else x.astype(jnp.bfloat16),
      meta.unbox(dec.model.init(
          jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))))
  row = _on_chip0(jax.eval_shape(lambda: tfm._zero_cache(dec.model, 1)))
  return dec, params, row, _on_chip0(jax.eval_shape(dec.init_slabs))


def trinity_decoder(slots: int = TRINITY_SLOTS,
                    max_seq: int = TRINITY_MAX_SEQ):
  """:func:`_sparse_decoder` at the Trinity cell's sizes."""
  return _sparse_decoder(trinity_cfg(max_seq), slots)


def t_serving_decode_trinity():
  """The cell trinity-serve-backlog's decode step at its real size: one
  whole-context leaf pair of 24 x 16384 x 1024 and four ring pairs of 24 x
  4096 x 1024 in the one slab (3.22 GB), 5 attention reads a step by the
  kernel that stops at the cursor (the rings with their skipped row), 32
  held experts a layer, horizon 4."""
  dec, params, _, slabs = trinity_decoder()
  return _step_many_target(dec, params, slabs)


#: the three largest shapes of that cell's prefill ladder
#: (``serving.slots.row_buckets(16384)``; trinity_prefill checks it)
TRINITY_BUCKETS = (2048, 1024, 512)


def trinity_prefill(bucket: int):
  """One of the same cell's largest prefill programs: a padded chunk of
  ``bucket`` tokens into a positional row of 16384; at a cursor above 0 (the
  same program: the cond's other branch) it attends the row in blocks of 2048
  through the flash kernel, so no float32 score tensor of bucket x 48 x 16384
  exists."""
  dec, params, row, _ = trinity_decoder()
  assert dec.buckets[:len(TRINITY_BUCKETS)] == TRINITY_BUCKETS, dec.buckets
  return dec._prefill_fn, (params, row, _i32(1, bucket), _i32())


def t_trinity_insert():
  """The same cell's insert: a positional row into a whole-context leaf
  pair and four rings (a gather of the window's last rows), the slab
  donated."""
  dec, _, row, slabs = trinity_decoder()
  return dec._insert_fn, (slabs, row, _i32())


#: the benchmark cell mimo-serve-backlog: slots x max_seq
MIMO_SLOTS, MIMO_MAX_SEQ = 48, 16384


def mimo_cfg(max_seq: int = MIMO_MAX_SEQ):
  """MiMo-V2-Flash as ``benchmarks/configs/mimo-v2-flash.json`` cuts it to
  one chip's share (published widths, published layers 0-6: the dense layer
  with full attention, then expert layers window x4, full, window; 16 of 256
  experts held, 1/8 of the vocabulary), spelled out so that the gate needs
  nothing of ``benchmarks/``; ``benchmarks/tests/test_mimo_v2_flash.py``
  keeps the two equal."""
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import transformer as tfm
  window = (False, True, True, True, True, False, True)
  return tfm.TransformerConfig(
      vocab_size=19072, num_layers=7, num_heads=64, attn_head_dim=192,
      attn_v_head_dim=128, layer_kv_heads=tuple(8 if w else 4 for w in window),
      rope_dim=64, layer_rope_theta=tuple(1e4 if w else 5e6 for w in window),
      layer_sink=window, attn_value_scale=0.707,
      d_model=4096, d_ff=16384, max_seq_len=max_seq, remat=False,
      dtype=jnp.bfloat16, ffn_types=("mlp",) + ("experts",) * 6,
      layer_windows=tuple(128 if w else 0 for w in window),
      norm="rms", norm_eps=1e-5, mlp_act="swiglu", tie_embeddings=False,
      experts_total=256, experts_held=16, experts_first=0, experts_top_k=8,
      experts_d_ff=2048, experts_shared=0, experts_scale=1.0)


def mimo_decoder(slots: int = MIMO_SLOTS, max_seq: int = MIMO_MAX_SEQ):
  """:func:`_sparse_decoder` at the MiMo cell's sizes."""
  return _sparse_decoder(mimo_cfg(max_seq), slots)


def t_serving_decode_mimo():
  """The cell mimo-serve-backlog's decode step at its real size: two
  whole-context leaf pairs of 48 x 16384 x 768 (K) and x 512 (V) and five
  ring pairs of 48 x 128 x 1536 and x 1024 in the one slab (4.18 GB: FOUR
  leaf shapes), 7 attention reads a step by the kernel that stops at the
  cursor (keys of 192 against values of 128; the rings ONE block with their
  skipped row and a sink a head), 14 leaf writes, 16 held experts a layer,
  horizon 4."""
  dec, params, _, slabs = mimo_decoder()
  return _step_many_target(dec, params, slabs)


#: the prefill shapes step zero compiles: the ladder's largest and 256
MIMO_BUCKETS = (2048, 256)


def mimo_prefill(bucket: int):
  """One of the same cell's prefill programs: a padded chunk of ``bucket``
  tokens into a positional row of 16384 (0.50 GB: leaves of four widths). The
  first chunk attends itself through the flash FORWARD at keys of 192 /
  values of 128 (a window layer's sink applied from ``(o, lse)``); at a cursor
  above 0 (the same program: the cond's other branch) it attends the row in
  blocks of 2048 through the same kernel, so no float32 score tensor of
  bucket x 64 x 16384 exists."""
  dec, params, row, _ = mimo_decoder()
  assert set(MIMO_BUCKETS) <= set(dec.buckets) \
      and dec.buckets[0] == MIMO_BUCKETS[0], dec.buckets
  return dec._prefill_fn, (params, row, _i32(1, bucket), _i32())


#: the benchmark cell deepseek-v3-serve-backlog: slots x max_seq
DEEPSEEK_SLOTS, DEEPSEEK_MAX_SEQ = 24, 16384


def deepseek_cfg(max_seq: int = DEEPSEEK_MAX_SEQ):
  """DeepSeek-V3 as ``benchmarks/configs/deepseek-v3.json`` cuts it to one
  chip's share (published widths, published layers 0 and 3-6: one dense layer
  and four expert layers; 16 of 256 experts held, 1/8 of the vocabulary),
  spelled out so that the gate needs nothing of ``benchmarks/``;
  ``benchmarks/tests/test_deepseek_v3.py`` keeps the two equal."""
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import transformer as tfm
  return tfm.TransformerConfig(
      vocab_size=16256, num_layers=5, num_heads=128, d_model=7168, d_ff=18432,
      max_seq_len=max_seq, remat=False, dtype=jnp.bfloat16,
      layer_types=("mla",) * 5, ffn_types=("mlp",) + ("experts",) * 4,
      norm="rms", norm_eps=1e-6, mlp_act="swiglu", tie_embeddings=False,
      mla_kv_rank=512, mla_nope_dim=128, mla_rope_dim=64, mla_v_dim=128,
      mla_q_rank=1536, mla_rope=True, rope_theta=10000.0,
      rope_yarn_factor=40.0, rope_yarn_original=4096, rope_yarn_beta_fast=32.0,
      rope_yarn_beta_slow=1.0, rope_yarn_mscale_all_dim=1.0,
      experts_total=256, experts_held=16, experts_first=0, experts_top_k=8,
      experts_d_ff=2048, experts_shared=1, experts_scale=2.5,
      experts_groups=8, experts_groups_kept=4)


def deepseek_decoder(slots: int = DEEPSEEK_SLOTS,
                     max_seq: int = DEEPSEEK_MAX_SEQ):
  """:func:`_sparse_decoder` at the DeepSeek cell's sizes."""
  return _sparse_decoder(deepseek_cfg(max_seq), slots)


def t_serving_decode_deepseek():
  """The cell deepseek-v3-serve-backlog's decode step at its real size: five
  latent leaves of 24 x 16384 x 640 in the one slab (2.52 GB), 5 reads a step
  by the kernel that stops at the cursor, handed the leaf as K and as V (128
  absorbed query heads over 640 lanes), 5 leaf writes, 16 held experts a layer
  under the group limit, horizon 4."""
  dec, params, _, slabs = deepseek_decoder()
  return _step_many_target(dec, params, slabs)


#: the prefill shapes step zero compiles: the ladder's largest and 256
DEEPSEEK_BUCKETS = (2048, 256)


def deepseek_prefill(bucket: int):
  """One of the same cell's prefill programs: a padded chunk of ``bucket``
  tokens into a positional row of 16384 (0.105 GB: five latent leaves). The
  first chunk attends itself through the flash FORWARD at 128 heads with keys
  of 192 / values of 128 expanded from the latent; at a cursor above 0 (the
  same program: the cond's other branch) it attends the row in blocks of 2048
  through the same kernel, each block's latent expanded as it is met, so no
  float32 score tensor of bucket x 128 x 16384 and no expanded row exist."""
  dec, params, row, _ = deepseek_decoder()
  assert set(DEEPSEEK_BUCKETS) <= set(dec.buckets) \
      and dec.buckets[0] == DEEPSEEK_BUCKETS[0], dec.buckets
  return dec._prefill_fn, (params, row, _i32(1, bucket), _i32())


#: the benchmark cell keye-vl2-serve-backlog: slots x max_seq
KEYE_SLOTS, KEYE_MAX_SEQ = 16, 32768


def keye_cfg(max_seq: int = KEYE_MAX_SEQ):
  """Keye-VL-2.0-30B-A3B's language model as ``benchmarks/configs/
  keye-vl-2.0-30b-a3b.json`` cuts it to one chip's share (published widths,
  published layers 0-5; 16 of 128 experts held, 1/8 of the vocabulary),
  spelled out so that the gate needs nothing of ``benchmarks/``;
  ``benchmarks/tests/test_keye_vl2.py`` keeps the two equal."""
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import transformer as tfm
  return tfm.TransformerConfig(
      vocab_size=19072, num_layers=6, num_heads=32, num_kv_heads=4,
      attn_head_dim=128, d_model=2048, d_ff=768, max_seq_len=max_seq,
      remat=False, dtype=jnp.bfloat16, ffn_types=("experts",) * 6,
      qk_norm=True, rope_theta=1e7, norm="rms", norm_eps=1e-6,
      mlp_act="swiglu", tie_embeddings=False,
      experts_total=128, experts_held=16, experts_first=0, experts_top_k=8,
      experts_d_ff=768, experts_shared=0, experts_score="softmax",
      sparse_topk=2048, index_heads=16, index_head_dim=64)


def keye_decoder(slots: int = KEYE_SLOTS, max_seq: int = KEYE_MAX_SEQ):
  """:func:`_sparse_decoder` at the Keye cell's sizes."""
  return _sparse_decoder(keye_cfg(max_seq), slots)


def t_serving_decode_keye():
  """The cell keye-vl2-serve-backlog's decode step at its real size: six
  layers of THREE leaves (K and V of 16 x 32768 x 512, the index key of 16 x
  32768 x 128: 7.25 GB), a step's 6 index-score reads of the index leaf, 6
  exact selections of 2048 among up to 32768 a lane, 6 attention reads by the
  kernel that stops at the cursor under the keep rows, 18 leaf writes, top 8
  of 16 held softmax experts a layer, horizon 4."""
  dec, params, _, slabs = keye_decoder()
  return _step_many_target(dec, params, slabs)


#: the prefill shapes step zero compiles: the ladder's largest and 256
KEYE_BUCKETS = (4096, 256)


def keye_prefill(bucket: int):
  """One of the same cell's prefill programs: a padded chunk of ``bucket``
  tokens into a positional row of 32768 (0.453 GB: three leaves a layer). A
  chunk that ends above 2048 positions scores its queries against the row's
  index keys in blocks of 2048 (``[bucket, 32768]`` float32 index scores, one
  head's worth; no ``[bucket, heads, 32768]`` tensor), selects a query, and
  attends under the keep operand: the first chunk itself through the flash
  forward, a later one its row in blocks of 2048 through the block call; one
  that ends at or below 2048 runs the branch without an indexer."""
  dec, params, row, _ = keye_decoder()
  assert set(KEYE_BUCKETS) <= set(dec.buckets) \
      and dec.buckets[0] == KEYE_BUCKETS[0], dec.buckets
  return dec._prefill_fn, (params, row, _i32(1, bucket), _i32())


def t_smoke_step_many():
  return _smoke_step_many(paged=False)


def t_smoke_paged_insert():
  dec, _, row, slabs = _smoke_decoder(paged=True)
  return dec._insert_pages_fn, (slabs, row, _i32(),
                                _i32(dec.pages_per_slot), _i32())


def t_smoke_paged_step_many():
  return _smoke_step_many(paged=True)


TARGETS = {
    "flash_mha_fwd": t_flash_mha_fwd,
    "flash_mha_fused_bwd": t_flash_mha_fused_bwd,
    "flash_mha_split_bwd": t_flash_mha_split_bwd,
    "flash_gqa_fused_bwd": t_flash_gqa_fused_bwd,
    "flash_gqa_split_bwd": t_flash_gqa_split_bwd,
    "flash_noncausal_fwd": t_flash_noncausal_fwd,
    "flash_short_seq_bwd": t_flash_short_seq_bwd,
    "flash_window_fused_bwd": t_flash_window_fused_bwd,
    "flash_window_gqa_split_bwd": t_flash_window_gqa_split_bwd,
    "ring_attention_window": t_ring_attention_window,
    "ring_attention_gqa": t_ring_attention_gqa,
    "layer_norm": t_layer_norm,
    "train_step": t_train_step,
    "serving_decode": t_serving_decode,
    "pipeline_1f1b": t_pipeline_1f1b,
    "pipeline_lm_flash": t_pipeline_lm_flash,
    "expert_a2a": t_expert_a2a,
    "serving_decode_int8": t_serving_decode_int8,
    "serving_speculative": t_serving_speculative,
    "serving_prefill_flash": t_serving_prefill_flash,
    "pipeline_gpipe": t_pipeline_gpipe,
    "train_step_pod": t_train_step_pod,
    "ring_attention_pod": t_ring_attention_pod,
    "resnet_bench": t_resnet_bench,
    "smoke_flash_fwd": t_smoke_flash_fwd,
    "smoke_flash_fused_bwd": t_smoke_flash_fused_bwd,
    "smoke_layer_norm": t_smoke_layer_norm,
    "smoke_train_loop": t_smoke_train_loop,
    "smoke_mesh_train_loop": t_smoke_mesh_train_loop,
    "smoke_insert": t_smoke_insert,
    "smoke_step_many": t_smoke_step_many,
    "smoke_paged_insert": t_smoke_paged_insert,
    "smoke_paged_step_many": t_smoke_paged_step_many,
    "gpt2l_step_many": t_gpt2l_step_many,
    "cursor_write": t_cursor_write,
    "decode_attention": t_decode_attention,
    "decode_attention_ouro": t_decode_attention_ouro,
    "gpt2l_prefill_512": t_gpt2l_prefill_512,
    "serving_decode_kimi_linear": t_serving_decode_kimi_linear,
    "serving_decode_ouro": t_serving_decode_ouro,
    "serving_decode_ouro_4_layers": t_serving_decode_ouro_4_layers,
    "ouro_prefill_512": t_ouro_prefill_512,
    "serving_decode_trinity": t_serving_decode_trinity,
    "trinity_insert": t_trinity_insert,
    "serving_decode_mimo": t_serving_decode_mimo,
    "serving_decode_deepseek": t_serving_decode_deepseek,
    "serving_decode_keye": t_serving_decode_keye,
}
TARGETS.update({"smoke_prefill_%d" % b: (lambda b=b: smoke_prefill(b))
                for b in SMOKE_BUCKETS})
TARGETS.update({"trinity_prefill_%d" % b: (lambda b=b: trinity_prefill(b))
                for b in TRINITY_BUCKETS})
TARGETS.update({"mimo_prefill_%d" % b: (lambda b=b: mimo_prefill(b))
                for b in MIMO_BUCKETS})
TARGETS.update({"deepseek_prefill_%d" % b: (lambda b=b: deepseek_prefill(b))
                for b in DEEPSEEK_BUCKETS})
TARGETS.update({"keye_prefill_%d" % b: (lambda b=b: keye_prefill(b))
                for b in KEYE_BUCKETS})
TARGETS.update({"kimi_linear_prefill_%d" % b:
                (lambda b=b: kimi_linear_prefill(b))
                for b in KIMI_LINEAR_BUCKETS})
TARGETS["kimi_linear_prefill_512_exact"] = \
    lambda: kimi_linear_prefill(512, padded=False)
TARGETS.update({"expert_product_%s_%s" % (name, kind):
                (lambda a=(g, d, f, rows): expert_product_target(*a))
                for name, (g, d, f, step, chunk) in EXPERT_PRODUCTS.items()
                for kind, rows in (("decode", step), ("chunk", chunk))})
TARGETS.update({"select_topk_keye_%s" % kind:
                (lambda rows=rows: select_topk_target(rows))
                for kind, rows in SELECT_TOPK.items()})

#: HBM of one v5e chip (Google Cloud "TPU v5e": 16 GB)
V5E_HBM_BYTES = 16 * 1024 ** 3


def entry_copies(hlo_text: str) -> dict:
  """``{result shape: count}`` of the plain ``copy`` instructions in the
  ENTRY computation: what a program moves at its own edges. A copy whose
  result has the shape of a whole argument (a KV slab leaf) is a relayout
  or an undonated update of it: the whole buffer read and written once a
  dispatch (PERF.md section 6, PR 25)."""
  import re
  start = hlo_text.find("\nENTRY ")
  if start < 0:
    return {}
  entry = hlo_text[start:hlo_text.index("\n}", start)]
  out = {}
  for shape in re.findall(r" = (\w+\[[\d,]*\])\S* copy\(", entry):
    out[shape] = out.get(shape, 0) + 1
  return out


def copies_back_to_hbm(hlo_text: str) -> dict:
  """``{shape: count}`` of the asynchronous copies whose DESTINATION is
  plain HBM (no ``S(n)`` memory space): buffers the compiler staged in fast
  memory, had written there, and so has to copy back whole. A KV slab leaf
  among them is read AND rewritten in full every decode step (PERF.md
  section 6, PR 25); a leaf that is only read in fast memory is not."""
  import re
  out = {}
  for shape, layout in re.findall(
      r"copy-start\.?\d* = \((\w+\[[\d,]*\])(\{[^}]*\})", hlo_text):
    if "S(" not in layout:
      out[shape] = out.get(shape, 0) + 1
  return out


def compiled_facts(compiled) -> dict:
  """What a deviceless compile can say about a program: per-device bytes
  (``memory_analysis``), whether the Pallas kernels are in
  (``tpu_custom_call``), which collectives the compiler put in, what the
  entry computation copies (:func:`entry_copies`) and what comes back
  from fast memory (:func:`copies_back_to_hbm`), and how many ``while``
  loops are left (a vmapped ``dynamic_update_slice`` is one a leaf)."""
  import re
  facts = {}
  m = compiled.memory_analysis()
  if m is not None:
    facts["memory_bytes"] = {
        k: int(getattr(m, k + "_size_in_bytes"))
        for k in ("argument", "output", "temp", "alias")}
    mb = facts["memory_bytes"]
    facts["device_bytes"] = (mb["argument"] + mb["output"] + mb["temp"]
                             - mb["alias"])
  text = compiled.as_text()
  facts["tpu_custom_calls"] = text.count("tpu_custom_call")
  facts["while_loops"] = len(re.findall(r" while\(", text))
  facts["collectives"] = {
      op: text.count(op + "(") + text.count(op + "-start(")
      for op in ("all-reduce", "all-gather", "reduce-scatter",
                 "collective-permute", "all-to-all")}
  facts["entry_copies"] = entry_copies(text)
  facts["copies_back_to_hbm"] = copies_back_to_hbm(text)
  return facts


def _abs_bench_step(batch, seq, cfg_kwargs, vocab, layers, heads, d_model,
                    d_ff, loss_impl="full"):
  """(jitted step, abstract args) for a single-chip train-step config:
  create_state + causal_lm_loss (or the blocked loss) + apply_gradients
  with eval_shape state, pinned to the 1-device topology mesh."""
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import transformer as tfm
  mesh = _mesh1()
  repl = _repl(mesh)
  cfg = tfm.TransformerConfig(
      vocab_size=vocab, num_layers=layers, num_heads=heads,
      d_model=d_model, d_ff=d_ff, max_seq_len=seq, **cfg_kwargs)
  abs_state = jax.eval_shape(
      lambda: tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=seq))

  def train_step(state, tokens):
    def loss_fn(params):
      if loss_impl == "blocked":
        hidden = state.apply_fn({"params": params}, tokens,
                                return_hidden=True)
        return tfm.causal_lm_loss_blocked(
            hidden, tfm.tied_embedding_table(params), tokens)
      logits = state.apply_fn({"params": params}, tokens)
      return tfm.causal_lm_loss(logits, tokens)
    loss, grads = jax.value_and_grad(loss_fn)(state.params)
    return state.apply_gradients(grads=grads), loss

  fn = jax.jit(train_step, in_shardings=(repl, repl),
               out_shardings=(repl, repl))
  tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
  return fn, (abs_state, tokens)


# The train step's candidates (fused-vs-flax LayerNorm, s=2048, selective
# remat, GQA) on the 12-layer transformer train step at its full width.
# This table is their compile evidence (SWEEP_COMPILE.json); their chip
# numbers come from the train cell's family, which takes the same
# overrides (benchmarks/families/gpt2.py `program_config`).
TFM_LAYERS, TFM_DMODEL, TFM_HEADS, TFM_DFF = 12, 768, 12, 3072
TFM_VOCAB, TFM_SEQ, TFM_BATCH = 32000, 1024, 16
SWEEP_CONFIGS = [
    ("b16_s1024_base", {}),
    ("b16_s1024_flaxln", {"layer_norm_impl": "flax"}),
    ("b8_s2048", {"batch": 8, "seq": 2048}),
    # selective remat: save MXU outputs, recompute elementwise only, to
    # reach the batches that do not fit without remat
    ("b24_s1024_rematdots", {"batch": 24, "remat": True,
                             "remat_policy": "dots"}),
    ("b32_s1024_rematdots", {"batch": 32, "remat": True,
                             "remat_policy": "dots"}),
    # GQA at this shape: 12 query heads on 4 KV heads
    ("b16_s1024_gqa4", {"num_kv_heads": 4}),
]


def run_bench_sweep_gate(json_path):
  """Compile-validate every SWEEP_CONFIGS candidate (plus the
  long-context step) against the deviceless topology, so the day they are
  measured on a real chip measures instead of debugging Mosaic rejections."""
  results = []
  for name, kw in SWEEP_CONFIGS:
    kw = dict(kw)
    batch = kw.pop("batch", TFM_BATCH)
    seq = kw.pop("seq", TFM_SEQ)
    kw.setdefault("remat", False)
    t0 = time.perf_counter()
    try:
      fn, args = _abs_bench_step(batch, seq, kw, TFM_VOCAB, TFM_LAYERS,
                                 TFM_HEADS, TFM_DMODEL, TFM_DFF)
      fn.lower(*args).compile()
      results.append(dict(config=name, ok=True,
                          seconds=round(time.perf_counter() - t0, 2)))
      print("PASS sweep:%-28s %.1fs" % (name, time.perf_counter() - t0),
            flush=True)
    except Exception as e:  # noqa: BLE001 - the error IS the result
      results.append(dict(config=name, ok=False, error=repr(e)[:800]))
      print("FAIL sweep:%-28s %s" % (name, repr(e)[:160]), flush=True)
  # the long-context headline config: s=4096 flash + blocked loss
  t0 = time.perf_counter()
  try:
    fn, args = _abs_bench_step(4, 4096, dict(remat=False), TFM_VOCAB,
                               4, 8, 1024, 4096, loss_impl="blocked")
    fn.lower(*args).compile()
    results.append(dict(config="long_context_s4096", ok=True,
                        seconds=round(time.perf_counter() - t0, 2)))
    print("PASS sweep:%-28s %.1fs"
          % ("long_context_s4096", time.perf_counter() - t0), flush=True)
  except Exception as e:  # noqa: BLE001
    results.append(dict(config="long_context_s4096", ok=False,
                        error=repr(e)[:800]))
    print("FAIL sweep:long_context_s4096 %s" % repr(e)[:160], flush=True)

  import jax
  n_fail = sum(1 for r in results if not r["ok"])
  with open(json_path, "w") as f:
    json.dump(dict(timestamp=time.strftime("%Y-%m-%dT%H:%M:%S"),
                   jax=jax.__version__,
                   mode="deviceless compile of bench sweep configs "
                        "(real kernels forced, 1-device v5e topology)",
                   passed=len(results) - n_fail, failed=n_fail,
                   results=results), f, indent=1)
  print("bench-sweep gate: %d/%d passed -> %s"
        % (len(results) - n_fail, len(results), json_path))
  return 1 if n_fail else 0


def run_tile_sweep_gate(json_path):
  """Compile-validate every tile candidate `tpu_validate.py --sweep-only`
  will time on-chip (same shapes, same per-kernel grids) so the auto-tune
  pass never wastes chip time on Mosaic-invalid tiles."""
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu.ops.flash_attention import flash_attention
  # ONE source of truth for shapes/grids: whatever the on-chip sweep will
  # time is exactly what this gate compile-validates
  from tools.tpu_validate import SWEEP_ATTN_SHAPE, SWEEP_FLASH_GRID
  mesh = _mesh1()
  repl = _repl(mesh)
  results = []

  def _compile(name, fn, args):
    t0 = time.perf_counter()
    try:
      fn.lower(*args).compile()
      results.append(dict(tile=name, ok=True,
                          seconds=round(time.perf_counter() - t0, 2)))
      print("PASS tile:%-34s %.1fs" % (name, time.perf_counter() - t0),
            flush=True)
    except Exception as e:  # noqa: BLE001 - the error IS the result
      results.append(dict(tile=name, ok=False, error=repr(e)[:400]))
      print("FAIL tile:%-34s %s" % (name, repr(e)[:140]), flush=True)

  b, s, h, d = SWEEP_ATTN_SHAPE
  q = _sh(b, s, h, d)
  for blk_q, blk_k in SWEEP_FLASH_GRID:
    _compile("flash_fwd[%dx%d]" % (blk_q, blk_k),
             jax.jit(lambda q, k, v, bq=blk_q, bk=blk_k: flash_attention(
                 q, k, v, causal=True, blk_q=bq, blk_k=bk),
                 in_shardings=(repl,) * 3), (q, q, q))
    for bwd in ("fused", "split"):
      _compile("flash_bwd_%s[%dx%d]" % (bwd, blk_q, blk_k),
               jax.jit(jax.grad(
                   lambda q, k, v, bq=blk_q, bk=blk_k, bm=bwd: jnp.sum(
                       flash_attention(q, k, v, causal=True, bwd=bm,
                                       blk_bwd_q=bq, blk_bwd_k=bk)
                       .astype(jnp.float32)), argnums=(0, 1, 2)),
                   in_shardings=(repl,) * 3), (q, q, q))

  n_fail = sum(1 for r in results if not r["ok"])
  with open(json_path, "w") as f:
    json.dump(dict(timestamp=time.strftime("%Y-%m-%dT%H:%M:%S"),
                   jax=jax.__version__,
                   mode="deviceless compile of tpu_validate --sweep-only "
                        "tile candidates (1-device v5e topology)",
                   passed=len(results) - n_fail, failed=n_fail,
                   results=results), f, indent=1)
  print("tile-sweep gate: %d/%d passed -> %s"
        % (len(results) - n_fail, len(results), json_path))
  return 1 if n_fail else 0


def run_gate(names):
  results = []
  for name in names:
    t0 = time.perf_counter()
    try:
      fn, args = TARGETS[name]()
      lowered = fn.lower(*args)
      t_lower = time.perf_counter() - t0
      t1 = time.perf_counter()
      compiled = lowered.compile()
      results.append(dict(target=name, ok=True,
                          lower_s=round(t_lower, 2),
                          compile_s=round(time.perf_counter() - t1, 2),
                          **compiled_facts(compiled)))
      print("PASS %-22s lower %.1fs compile %.1fs"
            % (name, t_lower, time.perf_counter() - t1), flush=True)
    except Exception as e:  # noqa: BLE001 - the error IS the result
      results.append(dict(target=name, ok=False,
                          seconds=round(time.perf_counter() - t0, 2),
                          error=repr(e)[:800]))
      print("FAIL %-22s %s" % (name, repr(e)[:200]), flush=True)
  return results


def main(argv=None):
  _ensure_clean_env()
  ap = argparse.ArgumentParser()
  ap.add_argument("--targets", default=None,
                  help="comma-separated subset (default: all)")
  ap.add_argument("--json", default=os.path.join(_REPO, "MOSAIC_GATE.json"))
  ap.add_argument("--list", action="store_true")
  ap.add_argument("--bench-sweep", action="store_true",
                  help="compile-validate every SWEEP_CONFIGS entry "
                       "instead of the kernel targets; writes "
                       "SWEEP_COMPILE.json")
  ap.add_argument("--tile-sweep", action="store_true",
                  help="compile-validate every tpu_validate --sweep-only "
                       "tile candidate; writes TILE_COMPILE.json")
  args = ap.parse_args(argv)
  if args.list:
    print("\n".join(TARGETS))
    return 0
  if args.bench_sweep:
    return run_bench_sweep_gate(os.path.join(_REPO, "SWEEP_COMPILE.json"))
  if args.tile_sweep:
    return run_tile_sweep_gate(os.path.join(_REPO, "TILE_COMPILE.json"))
  names = args.targets.split(",") if args.targets else list(TARGETS)
  unknown = [n for n in names if n not in TARGETS]
  if unknown:
    ap.error("unknown targets: %s" % ", ".join(unknown))
  if args.targets and args.json == os.path.join(_REPO, "MOSAIC_GATE.json"):
    # a subset run (triage, cache pre-warm) must not shrink the canonical
    # full-gate artifact to its few targets
    args.json = os.path.join(_REPO, "MOSAIC_GATE.partial.json")

  import jax
  results = run_gate(names)
  n_fail = sum(1 for r in results if not r["ok"])
  payload = dict(
      timestamp=time.strftime("%Y-%m-%dT%H:%M:%S"),
      jax=jax.__version__,
      topology="v5e (deviceless AOT: topologies.get_topology_desc)",
      mode="compile-only Mosaic lowering gate; no device claimed",
      passed=len(results) - n_fail, failed=n_fail, results=results)
  with open(args.json, "w") as f:
    json.dump(payload, f, indent=1)
  print("mosaic gate: %d/%d passed -> %s"
        % (len(results) - n_fail, len(results), args.json))
  return 1 if n_fail else 0


if __name__ == "__main__":
  sys.exit(main())
