#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that this system still starts on the chip.

Drives the repo's main path once, through the entry points a user calls, at
the full width of a GPT-2-small-class decoder (12 layers, d_model 768, 12
heads of 64, d_ff 3072, vocab 32000, bf16, ~124M parameters; weights random
from ``--seed``):

* **train** — ``cluster.run(LocalEngine(num_executors=1), ..., ENGINE input
  mode, feed_transport="shm", train_unroll=K)``; the driver feeds token rows
  through ``c.train``; the node builds ``create_state`` + a 1-device mesh +
  ``make_train_loop`` and consumes ``slab_batches(ctx.get_data_feed())`` for
  ``SLABS x UNROLL`` optimizer steps at batch 16 x sequence 1024.
* **serve** — a fresh child: ``ServingEngine`` (default cache layout) answers
  8 mixed-length requests, each compared token for token with a
  single-request ``greedy_generate_kv`` decode.

With ``--chips 4`` it runs the two legs that exist only across chips, and no
other phase: **allocation** (four executors x ``chips_per_node=1``, each on
its own chip) and **mesh** (one node owning four chips, ``data=2 x tensor=2``,
compared with one device of the same process).

One process per chip — the rules this script keeps, and states:

* this parent never initialises a JAX backend (it imports numpy and the
  orchestration layer only); all device work runs in the processes the
  normal entry points create, or in ``spawn`` children of this file (which
  is why it is a real file with a ``__main__`` guard: children re-import it);
* one executor per chip, phases strictly one after the other, and each
  phase's processes are gone (chip released) before the next starts;
* it never sets ``TOS_TPU_TEST_MODE`` or ``ALLOW_MULTIPLE_LIBTPU_LOAD``: on
  the chip machine libtpu's lock is what keeps two processes off one chip;
* the compile cache is placed by ``utils.compile_cache`` (where
  ``JAX_COMPILATION_CACHE_DIR`` says, else ``<repo>/.jax_cache``), the
  native ring is built from ``native/*.cpp`` by this run.

Any failed assertion, any phase that raised, any device other than a TPU:
non-zero exit and no ``"ok": true`` line. The last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and carries nothing more. Earlier lines are smoke timings, not metrics.

``--rehearse`` is the builder's CPU rehearsal (tiny widths, Pallas interpret
mode, no device claims): it never prints an ``"ok"`` line.
"""

import argparse
import json
import math
import multiprocessing
import os
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
if _REPO not in sys.path:
  sys.path.insert(0, _REPO)

# ---------------------------------------------------------------------------
# What runs. Widths are those of the 12-layer train step that
# tools/mosaic_gate.py compiles (TFM_*); nothing about them is cut on the
# chip. --rehearse swaps in toy widths for the CPU.
# ---------------------------------------------------------------------------

FULL = dict(
    widths=dict(vocab_size=32000, num_layers=12, num_heads=12, d_model=768,
                d_ff=3072),
    batch=16, seq=1024, unroll=4, slabs=2,
    serve_requests=8, serve_new_tokens=32, serve_prompt_range=(16, 512),
    serve_max_seq=1024,
    # "auto" picks the Pallas kernels on the TPU backend (ops/__init__.py);
    # the smoke asserts that policy rather than forcing it
    kernel_impl=dict(attention_impl="auto", layer_norm_impl="auto"),
)
TINY = dict(
    widths=dict(vocab_size=512, num_layers=2, num_heads=4, d_model=128,
                d_ff=256),
    batch=4, seq=128, unroll=2, slabs=2,
    serve_requests=4, serve_new_tokens=8, serve_prompt_range=(4, 48),
    serve_max_seq=128,
    # CPU: force the kernels (interpret mode) so the kernel-vs-XLA leg runs
    kernel_impl=dict(attention_impl="flash", layer_norm_impl="fused"),
)
XLA_IMPL = dict(attention_impl="dense", layer_norm_impl="flax")

#: the embedding init of models.transformer.TiedEmbed (normal, this stddev)
EMBED_INIT_STD = 0.02
#: kernel path vs XLA-only path, forward loss on the same params and batch:
#: both are bf16 programs whose f32 loss is a mean over B*S tokens; per-token
#: bf16 noise (~2^-8 relative on logits of magnitude ~1) averages down, and
#: what is left is systematic rounding between two attention/LayerNorm
#: orders — 2e-2 absolute on a loss of ~10.5 is 0.2%
KERNEL_VS_XLA_ATOL = 2e-2
#: sharded (data=2 x tensor=2) vs one device, per-step loss: tensor
#: parallelism splits every d_ff/heads contraction into two partial sums
#: added in another order, and the optimizer then amplifies the difference
#: over K steps — 5e-2 absolute on a loss of ~10.5
MESH_VS_ONE_ATOL = 5e-2
#: serving parity: a mismatch passes only at a near-tie. The referee is an
#: all-float32 XLA forward of the same weights; the engine's token and the
#: reference's must BOTH be among the referee's top two, and their f32
#: logits must differ by less than this — a tie a bf16 program may break
#: either way: logits here have magnitude 2-4, where bf16 spacing is
#: 2^-7..2^-6 (0.008-0.016); 4 spacings = 0.0625. (First chip run, seed 0:
#: 7 of 8 requests differ somewhere in 32 tokens, margins 0.001-0.016 —
#: random weights put ~12% of greedy decisions inside one bf16 spacing.)
NEAR_TIE_LOGIT_MARGIN = 0.0625

CHILD_TIMEOUT_S = 900
#: the whole script, compilation included: the contract allows 1200 s on
#: one chip; the four-chip legs get less (every second there costs four)
DEADLINE_S = {1: 1150, 4: 600}


def log(msg: str) -> None:
  print("[chip_smoke] %s" % msg, flush=True)


def init_loss_band(vocab: int, d_model: int):
  """Where the first training loss must lie, from the model's init alone.

  At init the final LayerNorm output has unit RMS and the tied table is
  N(0, 0.02^2), so logits are ~N(0, s^2) with s^2 = 0.02^2 * d_model, and
  the targets (random tokens) are independent of them:
  E[loss] = ln V + s^2/2 (the log-normal mean of the partition function).
  ln 32000 + 0.154 = 10.53 at the full widths. The band is +-0.35: far
  tighter than any broken path (NaN, zero logits -> exactly ln V is still
  inside; a saturated softmax or a wrong shift is not)."""
  center = math.log(vocab) + (EMBED_INIT_STD ** 2) * d_model / 2.0
  return center - 0.35, center + 0.35


# ---------------------------------------------------------------------------
# Shared by the children (all JAX work happens below this line, never in the
# parent).
# ---------------------------------------------------------------------------


def model_config(conf, max_seq_len: int, **overrides):
  from tensorflowonspark_tpu.models import transformer as tfm
  kw = dict(conf["widths"], max_seq_len=max_seq_len, remat=False)
  kw.update(conf["kernel_impl"])
  kw.update(overrides)
  return tfm.TransformerConfig(**kw)


def lm_loss_fn(cfg, mesh=None):
  """``loss_fn(params, tokens)`` — the causal LM loss of the train step."""
  from tensorflowonspark_tpu.models import transformer as tfm
  model = tfm.Transformer(cfg, mesh)

  def loss_fn(params, tokens):
    return tfm.causal_lm_loss(model.apply({"params": params}, tokens), tokens)
  return loss_fn


def one_device_train(conf, unroll=None):
  """(cfg, state, loop): ``create_state`` + a 1-device mesh +
  ``make_train_loop`` on this process's first chip. ``unroll=None`` takes
  cluster.run(train_unroll=K)'s TOS_TRAIN_UNROLL, as a user's fn would."""
  import jax
  from tensorflowonspark_tpu.models import transformer as tfm
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib
  from tensorflowonspark_tpu.parallel import sharding as SH
  cfg = model_config(conf, conf["seq"])
  state = tfm.create_state(jax.random.PRNGKey(conf["seed"]), cfg,
                           seq_len=conf["seq"])
  mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=-1),
                             devices=jax.local_devices()[:1])
  return cfg, state, SH.make_train_loop(lm_loss_fn(cfg), mesh, unroll=unroll)


def _device_record(conf):
  """The device as JAX reports it; refuses anything but a TPU (and checks
  the interpret/kernels policy of ops/__init__.py) outside --rehearse."""
  import jax
  from tensorflowonspark_tpu import ops
  d = jax.devices()[0]
  rec = {"platform": d.platform, "kind": d.device_kind,
         "count": len(jax.devices()),
         "local": [[x.id, list(getattr(x, "coords", ()))]
                   for x in jax.local_devices()]}
  if not conf["rehearse"]:
    assert d.platform == "tpu", \
        "chip smoke needs a TPU, JAX found %r (%s)" % (d.platform,
                                                       d.device_kind)
    assert not ops.pallas_interpret(), \
        "Pallas kernels are in interpret mode on the chip path"
    assert ops.pallas_kernels_enabled(), \
        "'auto' would drop the Pallas kernels on the chip path"
  return rec


def _peak_bytes():
  import jax
  out = []
  for d in jax.local_devices():
    stats = d.memory_stats() or {}
    out.append(int(stats.get("peak_bytes_in_use", 0)))
  return out


def _write_report(conf, name: str, report: dict) -> None:
  path = os.path.join(conf["report_dir"], name + ".json")
  with open(path + ".tmp", "w") as f:
    json.dump(report, f)
  os.replace(path + ".tmp", path)


def _versions():
  import jax
  import jaxlib
  try:
    import libtpu
    lt = getattr(libtpu, "__version__", "?")
  except ImportError:
    lt = "absent"
  return {"jax": jax.__version__, "jaxlib": jaxlib.__version__, "libtpu": lt}


def _compile_timed(lowered, hits):
  """(compiled, seconds, was_cache_hit) for one AOT compile."""
  h0 = hits.hits
  t0 = time.time()
  compiled = lowered.compile()
  return compiled, round(time.time() - t0, 2), hits.hits > h0


# ---------------------------------------------------------------------------
# train phase: the node's main fn (runs in the process cluster.run spawns)
# ---------------------------------------------------------------------------


def train_main(conf, ctx):
  """ENGINE-mode node fn: feed -> slabs -> make_train_loop, with the
  assertions that must hold INSIDE the process that owns the chip."""
  import itertools
  import dataclasses
  import numpy as np
  import jax
  from tensorflowonspark_tpu import node as node_mod
  from tensorflowonspark_tpu.data import readers
  from tensorflowonspark_tpu.utils import compile_cache
  from tools.mosaic_gate import compiled_facts

  t_start = time.time()
  hits = compile_cache.HitCounter()    # node bring-up placed the cache
  dev = _device_record(conf)
  B, S, K = conf["batch"], conf["seq"], conf["unroll"]
  cfg, state, loop = one_device_train(conf)
  n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(state.params))
  assert loop.unroll == K, (loop.unroll, K)

  feed = ctx.get_data_feed(train_mode=True)
  items = readers.slab_batches(feed, B)
  first = next(items)
  assert isinstance(first, readers.Slab), type(first)
  assert first.data.shape == (K, B, S), first.data.shape

  # kernel path vs XLA-only path: forward loss, same params, same batch,
  # before any update (the loop donates its state)
  batch0 = jax.device_put(first.data[0])
  fwd = {}
  for name, c in (("kernel", cfg),
                  ("xla", dataclasses.replace(cfg, **XLA_IMPL))):
    t0 = time.time()
    fwd[name] = float(jax.jit(lm_loss_fn(c))(state.params, batch0))
    fwd[name + "_s"] = round(time.time() - t0, 2)
  assert abs(fwd["kernel"] - fwd["xla"]) <= KERNEL_VS_XLA_ATOL, fwd

  # the compiled train program itself: its text must hold the kernels.
  # Lowered on the device-resident slab, exactly what the loop is then
  # called with (a committed argument is part of the cache key)
  first = jax.device_put(first)
  compiled, compile_s, was_hit = _compile_timed(loop.lower(state, first), hits)
  program = compiled_facts(compiled)
  del compiled
  if not conf["rehearse"]:
    assert program["tpu_custom_calls"] > 0, \
        "no tpu_custom_call in the compiled train program"

  losses, rows, step_s = [], 0, []
  h0 = hits.hits
  for item in readers.device_prefetch(itertools.chain([first], items),
                                      size=2):
    t0 = time.time()
    state, ls = loop(state, item)
    ls = np.asarray(ls)                 # the fetch is the sync
    step_s.append(round(time.time() - t0, 3))
    losses.extend(float(x) for x in ls.reshape(-1))
    n = len(ls.reshape(-1))
    rows += n * B
  assert len(losses) >= conf["min_steps"], losses
  assert all(math.isfinite(x) for x in losses), losses
  lo, hi = init_loss_band(cfg.vocab_size, cfg.d_model)
  assert lo <= losses[0] <= hi, \
      "first loss %.4f outside the init band [%.2f, %.2f]" % (losses[0],
                                                             lo, hi)
  assert abs(losses[0] - fwd["kernel"]) <= KERNEL_VS_XLA_ATOL, \
      (losses[0], fwd)

  chan = feed._queue_in
  assert isinstance(chan, node_mod.DualInput), \
      "the shm ring was asked for, the feed rides %r" % type(chan).__name__
  report = dict(
      device=dev, versions=_versions(), pid=os.getpid(), n_params=n_params,
      steps=loop.steps, rows_consumed=rows, losses=losses,
      init_band=[lo, hi], forward_loss=fwd,
      deliveries=dict(chan.deliveries),
      train_program=program, train_loop_compile_s=compile_s,
      train_loop_compile_was_cache_hit=was_hit,
      dispatch_loaded_the_aot_compile=hits.hits > h0,
      cache=dict(dir=compile_cache.cache_dir(), hits=hits.hits,
                 misses=hits.misses),
      peak_bytes_in_use=_peak_bytes(),
      dispatch_seconds_smoke_timing=step_s,
      node_seconds=round(time.time() - t_start, 1))
  _write_report(conf, "train_node_%d" % ctx.executor_id, report)


def probe_train_cache(conf):
  """A SECOND process builds the same train program: did it load from the
  persistent cache instead of compiling?"""
  import numpy as np
  import jax
  from tensorflowonspark_tpu.data import readers
  from tensorflowonspark_tpu.utils import compile_cache
  compile_cache.setup()
  hits = compile_cache.HitCounter()
  _device_record(conf)
  B, S, K = conf["batch"], conf["seq"], conf["unroll"]
  _, state, loop = one_device_train(conf, unroll=K)
  slab = jax.device_put(readers.Slab(np.zeros((K, B, S), np.int32)))
  _, secs, was_hit = _compile_timed(loop.lower(state, slab), hits)
  _write_report(conf, "train_cache_probe",
                dict(compile_s=secs, cache_hit=was_hit, hits=hits.hits,
                     misses=hits.misses))


# ---------------------------------------------------------------------------
# serve phase (a fresh spawn child of this file)
# ---------------------------------------------------------------------------


def _serve_setup(conf):
  import numpy as np
  import jax
  import jax.numpy as jnp
  from flax.core import meta
  from tensorflowonspark_tpu.models import transformer as tfm
  # the rehearsal forces kernels only where training needs them compared
  auto = dict(attention_impl="auto", layer_norm_impl="auto")
  cfg = model_config(conf, conf["serve_max_seq"], **auto)
  model = tfm.Transformer(cfg)
  params = meta.unbox(model.init(
      jax.random.PRNGKey(conf["seed"]),
      jnp.zeros((1, 8), jnp.int32))["params"])
  rng = np.random.RandomState(conf["seed"])
  lo, hi = conf["serve_prompt_range"]
  prompts = [rng.randint(0, cfg.vocab_size, (int(n),)).astype(np.int32)
             for n in rng.randint(lo, hi + 1, conf["serve_requests"])]
  return cfg, params, prompts


def _f32_margin(cfg, params, tokens, pos, ref_tok, eng_tok):
  """The all-float32 XLA referee: logits for position ``pos`` given
  ``tokens[:pos]``; returns (z[ref] - z[engine], top1 - top2, whether
  the two candidates ARE the referee's top two)."""
  import dataclasses
  import numpy as np
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import transformer as tfm
  f32 = dataclasses.replace(cfg, dtype=jnp.float32, **XLA_IMPL)
  model = tfm.Transformer(f32)
  buf = np.zeros((1, cfg.max_seq_len), np.int32)
  buf[0, :pos] = tokens[:pos]          # causal: the zero tail cannot leak
  logits = jax.jit(lambda p, t: model.apply({"params": p}, t))(
      params, jnp.asarray(buf))
  z = np.asarray(logits[0, pos - 1], np.float32)
  top2 = np.argsort(z)[-2:]
  return (float(z[ref_tok] - z[eng_tok]), float(z[top2[1]] - z[top2[0]]),
          {int(ref_tok), int(eng_tok)} == {int(t) for t in top2})


def serve_child(conf):
  import numpy as np
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu import serving
  from tensorflowonspark_tpu.models import transformer as tfm
  from tensorflowonspark_tpu.utils import compile_cache
  t_start = time.time()
  compile_cache.setup()
  hits = compile_cache.HitCounter()
  dev = _device_record(conf)
  cfg, params, prompts = _serve_setup(conf)
  new = conf["serve_new_tokens"]

  # max_restarts=0: a lowering error or a lost chip must surface as what
  # it is, not be absorbed by the crash-replay loop as "infrastructure"
  eng = serving.ServingEngine(params, cfg, max_new_tokens=new,
                              max_restarts=0).start()
  assert eng.page_size == 0, "default cache layout is the contiguous slab"
  t0 = time.time()
  rids = [eng.submit(p, max_new_tokens=new) for p in prompts]
  outs = [np.asarray(eng.result(r, timeout=CHILD_TIMEOUT_S)) for r in rids]
  serve_s = round(time.time() - t0, 2)
  stats = dict(eng.stats)
  eng.stop()
  assert stats["engine_restarts"] == 0, stats
  assert stats["replay_mismatches"] == 0, stats

  t0 = time.time()
  mismatches = []
  for i, (p, out) in enumerate(zip(prompts, outs)):
    ref = np.asarray(tfm.greedy_generate_kv(
        params, cfg, jnp.asarray(p)[None], new))[0]
    assert out.shape == ref.shape == (len(p) + new,), (out.shape, ref.shape)
    assert np.array_equal(out[:len(p)], p)
    diff = np.nonzero(out != ref)[0]
    if len(diff):
      pos = int(diff[0])
      margin, top_gap, are_top2 = _f32_margin(
          cfg, params, ref, pos, int(ref[pos]), int(out[pos]))
      mismatches.append(dict(
          request=i, prompt_len=len(p), first_diff_position=pos,
          generated_index=pos - len(p), ref_token=int(ref[pos]),
          engine_token=int(out[pos]), f32_logit_margin=margin,
          f32_top1_top2_gap=top_gap,
          candidates_are_f32_top2=are_top2,
          near_tie=are_top2 and abs(margin) <= NEAR_TIE_LOGIT_MARGIN))
      log("serve parity MISMATCH %s" % json.dumps(mismatches[-1]))
  ref_s = round(time.time() - t0, 2)
  bad = [m for m in mismatches if not m["near_tie"]]
  assert not bad, "serving mismatches beyond a near-tie (margin %g): %r" % (
      NEAR_TIE_LOGIT_MARGIN, bad)
  _write_report(conf, "serve", dict(
      device=dev, prompt_lens=[len(p) for p in prompts], new_tokens=new,
      requests=len(prompts), token_identical=len(prompts) - len(mismatches),
      mismatches=mismatches, near_tie_margin=NEAR_TIE_LOGIT_MARGIN,
      stats={k: v for k, v in stats.items()
             if isinstance(v, (int, float))},
      cache=dict(dir=compile_cache.cache_dir(), hits=hits.hits,
                 misses=hits.misses),
      peak_bytes_in_use=_peak_bytes(),
      serve_seconds_smoke_timing=serve_s,
      reference_seconds_smoke_timing=ref_s,
      child_seconds=round(time.time() - t_start, 1)))


def probe_serve_cache(conf):
  """A SECOND process builds the engine's fused decode program (same
  SlotDecoder, same shapes): loaded from the cache, or compiled again?"""
  import numpy as np
  import jax.numpy as jnp
  from tensorflowonspark_tpu import serving
  from tensorflowonspark_tpu.serving import slots as slots_lib
  from tensorflowonspark_tpu.utils import compile_cache
  compile_cache.setup()
  hits = compile_cache.HitCounter()
  _device_record(conf)
  cfg, params, _ = _serve_setup(conf)
  eng = serving.ServingEngine(params, cfg, max_restarts=0)   # not started
  dec = slots_lib.SlotDecoder(cfg, eng.num_slots)
  slabs = dec.init_slabs()
  n = eng.num_slots
  t0 = time.time()
  out = dec.step_many(params, slabs, np.zeros(n, np.int32),
                      np.zeros(n, bool), np.zeros(n, np.int32), eng.horizon)
  jnp.asarray(out[1]).block_until_ready()
  _write_report(conf, "serve_cache_probe",
                dict(compile_s=round(time.time() - t0, 2),
                     cache_hit=hits.hits > 0, hits=hits.hits,
                     misses=hits.misses))


# ---------------------------------------------------------------------------
# --chips 4: allocation leg (node fn) and mesh leg (node fn)
# ---------------------------------------------------------------------------


def alloc_main(conf, ctx):
  """One of N executors x chips_per_node=1: report the chip this process
  was given and take two train steps on it at the full widths."""
  try:
    _alloc_steps(conf, ctx)
  except BaseException:
    # a report either way, so the driver stops waiting; the error itself
    # still travels the node's error queue and fails c.shutdown()
    import traceback
    _write_report(conf, "alloc_node_%d" % ctx.executor_id,
                  {"error": traceback.format_exc()})
    raise
  # stay until the driver has seen every node's report: in libtpu's
  # multi-process form a peer that leaves early can take the slice down
  feed = ctx.get_data_feed(train_mode=True)
  while not feed.should_stop():
    feed.next_batch(1)


def _alloc_steps(conf, ctx):
  import numpy as np
  import jax
  from tensorflowonspark_tpu.data import readers
  log("alloc node %d: pid %d, TPU_VISIBLE_CHIPS=%s, initialising JAX ..."
      % (ctx.executor_id, os.getpid(), os.environ.get("TPU_VISIBLE_CHIPS")))
  dev = _device_record(conf)
  local = jax.local_devices()
  log("alloc node %d: local devices %r of %d" % (ctx.executor_id, local,
                                                 dev["count"]))
  B, S = conf["batch"], conf["seq"]
  cfg, state, loop = one_device_train(conf, unroll=2)
  rng = np.random.RandomState(conf["seed"] + ctx.executor_id)
  slab = readers.Slab(rng.randint(0, cfg.vocab_size,
                                  (2, B, S)).astype(np.int32))
  state, losses = loop(state, jax.device_put(slab))
  losses = [float(x) for x in np.asarray(losses)]
  log("alloc node %d: two steps done, losses %r" % (ctx.executor_id, losses))
  assert all(math.isfinite(x) for x in losses), losses
  lo, hi = init_loss_band(cfg.vocab_size, cfg.d_model)
  assert lo <= losses[0] <= hi, (losses, lo, hi)
  env = {k: os.environ.get(k) for k in
         ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
          "TPU_PROCESS_BOUNDS", "TPU_PROCESS_ADDRESSES", "TPU_PROCESS_PORT",
          "CLOUD_TPU_TASK_ID")}
  _write_report(conf, "alloc_node_%d" % ctx.executor_id, dict(
      device=dev, pid=os.getpid(), n_local=len(local), chip_env=env,
      process_index=jax.process_index(), losses=losses,
      peak_bytes_in_use=_peak_bytes()))


def mesh_main(conf, ctx):
  """One node owning every chip: create_sharded_state + make_train_loop on
  data=2 x tensor=2, compared with one device of this same process."""
  import numpy as np
  import jax
  from tensorflowonspark_tpu.data import readers
  from tensorflowonspark_tpu.models import transformer as tfm
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib
  from tensorflowonspark_tpu.parallel import sharding as SH
  from tensorflowonspark_tpu.utils import compile_cache
  from tools.mosaic_gate import compiled_facts
  hits = compile_cache.HitCounter()
  dev = _device_record(conf)
  devices = jax.local_devices()
  assert len(devices) == conf["chips"], (len(devices), conf["chips"])
  B, S, K = conf["batch"], conf["seq"], conf["unroll"]
  key = jax.random.PRNGKey(conf["seed"])
  rng = np.random.RandomState(conf["seed"])
  vocab = conf["widths"]["vocab_size"]
  slab = readers.Slab(rng.randint(0, vocab, (K, B, S)).astype(np.int32))

  mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=2, tensor=2),
                             devices=devices)
  cfg = model_config(conf, S)
  state, sharding = tfm.create_sharded_state(key, cfg, mesh, seq_len=S)
  loop = SH.make_train_loop(lm_loss_fn(cfg, mesh), mesh, sharding, unroll=K)
  dslab = jax.device_put(slab, SH.slab_sharding(mesh))
  log("mesh node: sharded state up on %d devices, compiling ..."
      % len(devices))
  compiled, compile_s, was_hit = _compile_timed(loop.lower(state, dslab),
                                                hits)
  log("mesh node: compiled in %.1fs" % compile_s)
  program = compiled_facts(compiled)
  del compiled
  if not conf["rehearse"]:
    assert program["tpu_custom_calls"] > 0, \
        "no tpu_custom_call in the sharded train program"
  assert sum(program["collectives"].values()) > 0, program

  # spread, not parked: a tensor-sharded parameter and the batch
  up = state.params["layer_0"]["mlp"]["up"]["kernel"]
  shards = up.addressable_shards
  assert len({s.device.id for s in shards}) == len(devices), shards
  assert all(s.data.shape[-1] * 2 == up.shape[-1] for s in shards), \
      [s.data.shape for s in shards]
  bshards = dslab.data.addressable_shards
  assert len({s.device.id for s in bshards}) == len(devices)
  assert all(s.data.shape == (K, B // 2, S) for s in bshards), \
      [s.data.shape for s in bshards]
  in_use = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
            for d in devices]
  if not conf["rehearse"]:
    assert min(in_use) > 0 and max(in_use) <= 2 * min(in_use), \
        "state parked unevenly across devices: %r" % in_use

  state, sharded_losses = loop(state, dslab)
  sharded_losses = [float(x) for x in np.asarray(sharded_losses)]
  log("mesh node: sharded losses %r" % sharded_losses)
  peak_sharded = _peak_bytes()
  del state

  # what it is compared with: the same seed on ONE device of this process
  _, state1, loop1 = one_device_train(conf, unroll=K)
  state1, one_losses = loop1(state1, jax.device_put(slab, devices[0]))
  one_losses = [float(x) for x in np.asarray(one_losses)]
  assert all(math.isfinite(x) for x in sharded_losses + one_losses)
  lo, hi = init_loss_band(cfg.vocab_size, cfg.d_model)
  assert lo <= sharded_losses[0] <= hi, (sharded_losses, lo, hi)
  worst = max(abs(a - b) for a, b in zip(sharded_losses, one_losses))
  assert worst <= MESH_VS_ONE_ATOL, (sharded_losses, one_losses)
  _write_report(conf, "mesh_node", dict(
      device=dev, mesh={"data": 2, "tensor": 2},
      sharded_losses=sharded_losses, one_device_losses=one_losses,
      max_abs_loss_diff=worst, tolerance=MESH_VS_ONE_ATOL,
      sharded_program_per_device=program,
      compile_s=compile_s, compile_was_cache_hit=was_hit,
      bytes_in_use_after_init=in_use, peak_bytes_sharded=peak_sharded,
      up_kernel_shard_shapes=[list(s.data.shape) for s in shards],
      batch_shard_shapes=[list(s.data.shape) for s in bshards]))


# ---------------------------------------------------------------------------
# The parent: orchestration only. No JAX below this line.
# ---------------------------------------------------------------------------


def _read_report(conf, name: str) -> dict:
  path = os.path.join(conf["report_dir"], name + ".json")
  assert os.path.exists(path), "phase left no report %s" % path
  with open(path) as f:
    return json.load(f)


def _pid_gone(pid: int) -> bool:
  try:
    with open("/proc/%d/stat" % pid) as f:
      return f.read().rsplit(")", 1)[1].split()[0] == "Z"
  except OSError:
    return True


def _wait_gone(pids, what: str, timeout: float = 30.0) -> None:
  deadline = time.time() + timeout
  while time.time() < deadline:
    if all(_pid_gone(p) for p in pids):
      return
    time.sleep(0.2)
  raise AssertionError("%s still running: %r"
                       % (what, [p for p in pids if not _pid_gone(p)]))


def run_child(target, conf, what: str) -> None:
  """One spawn child of this file; raises unless it exits 0 in time."""
  ctx = multiprocessing.get_context("spawn")
  proc = ctx.Process(target=target, args=(conf,), name="smoke-" + what)
  proc.start()
  proc.join(CHILD_TIMEOUT_S)
  if proc.is_alive():
    proc.kill()
    proc.join(10)
    raise RuntimeError("%s child exceeded %ds" % (what, CHILD_TIMEOUT_S))
  if proc.exitcode != 0:
    raise RuntimeError("%s child exited %r" % (what, proc.exitcode))
  _wait_gone([proc.pid], what)


def _token_partitions(conf, n_rows: int, n_parts: int):
  import numpy as np
  rng = np.random.RandomState(conf["seed"])
  rows = rng.randint(0, conf["widths"]["vocab_size"],
                     (n_rows, conf["seq"])).astype(np.int32)
  per = n_rows // n_parts
  return [list(rows[i * per:(i + 1) * per]) for i in range(n_parts)]


def _run_cluster(conf, main_fn, num_executors: int, feed_rows=None,
                 wait_reports=None, wait_s: float = 240.0, **run_kw):
  """cluster.run -> (feed) -> shutdown on a LocalEngine; returns the
  cluster after asserting nothing was relaunched and every process the
  engine started is gone."""
  from tensorflowonspark_tpu import cluster
  from tensorflowonspark_tpu.engine import LocalEngine
  engine = LocalEngine(num_executors=num_executors)
  executor_pids = [p.pid for p in engine._procs]
  try:
    # max_restarts=0: a lost chip must fail the smoke, not be relaunched
    t_run = time.monotonic()
    c = cluster.run(engine, main_fn, tf_args=conf, max_restarts=0,
                    reservation_timeout=300, **run_kw)
    if feed_rows is not None:
      c.train(feed_rows, num_epochs=1, feed_timeout=CHILD_TIMEOUT_S)
    if wait_reports:
      deadline = time.time() + wait_s
      while not all(os.path.exists(os.path.join(conf["report_dir"], n +
                                                ".json"))
                    for n in wait_reports):
        missing = [n for n in wait_reports if not os.path.exists(
            os.path.join(conf["report_dir"], n + ".json"))]
        assert time.time() < deadline, \
            "nodes never reported within %ds: %r" % (wait_s, missing)
        time.sleep(0.5)
    try:
      c.shutdown(timeout=CHILD_TIMEOUT_S)
    finally:
      # when a node was declared dead, WHEN is the first question
      if c.supervisor.events:
        log("supervisor events (t = seconds after cluster.run): %s"
            % json.dumps([dict(e, t=round(e["t"] - t_run, 1))
                          for e in c.supervisor.events], default=str))
    assert c.supervisor.restarts == {}, c.supervisor.restarts
  finally:
    engine.stop()
  _wait_gone(executor_pids, "LocalEngine executors")
  return c


def phase_train(conf) -> dict:
  from tensorflowonspark_tpu.cluster import InputMode
  B, K, slabs = conf["batch"], conf["unroll"], conf["slabs"]
  rows_fed = B * K * slabs
  parts = _token_partitions(conf, rows_fed, n_parts=slabs * 2)
  t0 = time.time()
  _run_cluster(conf, train_main, 1, feed_rows=parts,
               input_mode=InputMode.ENGINE, feed_transport="shm",
               train_unroll=K)
  rep = _read_report(conf, "train_node_0")
  _wait_gone([rep["pid"]], "train node process")
  assert rep["rows_consumed"] == rows_fed, (rep["rows_consumed"], rows_fed)
  assert rep["steps"] == K * slabs >= conf["min_steps"], rep["steps"]
  d = rep["deliveries"]
  assert d["ring"] > 0 and d["queue"] == 0, \
      "the shm ring was asked for but deliveries were %r" % d
  rep["phase_seconds"] = round(time.time() - t0, 1)
  run_child(probe_train_cache, conf, "train-cache-probe")
  rep["second_process"] = _read_report(conf, "train_cache_probe")
  return rep


def phase_serve(conf) -> dict:
  t0 = time.time()
  run_child(serve_child, conf, "serve")
  rep = _read_report(conf, "serve")
  assert rep["token_identical"] + len(rep["mismatches"]) == rep["requests"]
  rep["phase_seconds"] = round(time.time() - t0, 1)
  run_child(probe_serve_cache, conf, "serve-cache-probe")
  rep["second_process"] = _read_report(conf, "serve_cache_probe")
  return rep


def phase_allocation(conf) -> dict:
  from tensorflowonspark_tpu.cluster import InputMode
  n = conf["chips"]
  names = ["alloc_node_%d" % i for i in range(n)]
  # (the CPU rehearsal has no chips to claim: claim_chips would refuse)
  _run_cluster(conf, alloc_main, n, wait_reports=names,
               input_mode=InputMode.ENGINE,
               chips_per_node=0 if conf["rehearse"] else 1)
  reps = [_read_report(conf, name) for name in names]
  assert not any("error" in r for r in reps), reps
  if not conf["rehearse"]:
    assert all(r["n_local"] == 1 for r in reps), \
        [r["device"]["local"] for r in reps]
    chips = [tuple(r["device"]["local"][0][1]) or r["device"]["local"][0][0]
             for r in reps]
    assert len(set(chips)) == n, "executors share chips: %r" % (chips,)
    assert len({r["chip_env"]["TPU_VISIBLE_CHIPS"] for r in reps}) == n
  _wait_gone([r["pid"] for r in reps], "allocation node processes")
  return {"device": reps[0]["device"], "nodes": reps}


def phase_mesh(conf) -> dict:
  from tensorflowonspark_tpu.cluster import InputMode
  _run_cluster(conf, mesh_main, 1, input_mode=InputMode.FILES)
  return _read_report(conf, "mesh_node")


PHASES = {"train": phase_train, "serve": phase_serve,
          "allocation": phase_allocation, "mesh": phase_mesh}


def preflight(conf) -> None:
  """Fail at once, without JAX, where no chip can be had."""
  from tensorflowonspark_tpu.utils import tpu_info
  for var in ("TOS_TPU_TEST_MODE", "ALLOW_MULTIPLE_LIBTPU_LOAD"):
    assert not os.environ.get(var), \
        "%s is set: the chip smoke refuses to run under it" % var
  plat = os.environ.get("JAX_PLATFORMS", "")
  assert not plat or "tpu" in plat.split(","), \
      "JAX is held to JAX_PLATFORMS=%r: no accelerator" % plat
  topo = tpu_info.get_topology()
  assert topo is not None, \
      "no accelerator: TPU_ACCELERATOR_TYPE unset and no chip device node"
  present = tpu_info.local_chip_count()
  log("topology without JAX: %r; chip device nodes here: %d"
      % (topo, present))
  assert (present or topo.chips_per_host) >= conf["chips"], \
      "--chips %d asked, this host shows %d" % (conf["chips"], present)
  assert "jax" not in sys.modules, "the parent must stay off JAX"


def _kill_stragglers() -> int:
  """SIGKILL every OTHER process of this script's process group: whatever a
  failed phase left behind (a node child stuck in libtpu outlives its
  terminated executor, and would hold this script's stdout open)."""
  import signal
  me, group, n = os.getpid(), os.getpgrp(), 0
  for entry in os.listdir("/proc"):
    if not entry.isdigit() or int(entry) == me:
      continue
    try:
      if os.getpgid(int(entry)) == group:
        os.kill(int(entry), signal.SIGKILL)
        n += 1
    except OSError:
      continue
  return n


def _arm_deadline(seconds: float) -> None:
  import threading

  def _expired():
    log("DEADLINE: %ds passed; killing every process and failing" % seconds)
    sys.stdout.flush()
    _kill_stragglers()
    os._exit(124)

  t = threading.Timer(seconds, _expired)
  t.daemon = True
  t.start()


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--seed", type=int, default=0)
  ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                  help="4: run the allocation and mesh legs, and no other")
  ap.add_argument("--phase", action="append", choices=sorted(PHASES),
                  help="builder's debugging: run only these phases (no "
                       "'ok' line is printed for a partial run)")
  ap.add_argument("--rehearse", action="store_true",
                  help="CPU rehearsal at toy widths; never prints 'ok'")
  args = ap.parse_args(argv)
  if not __debug__:
    sys.exit("chip_smoke's checks are assert statements: run it without "
             "-O / PYTHONOPTIMIZE, or it would check nothing")

  conf = dict(TINY if args.rehearse else FULL)
  conf.update(seed=args.seed, chips=args.chips, rehearse=args.rehearse,
              min_steps=4 if args.rehearse else 8,
              report_dir=tempfile.mkdtemp(prefix="chip_smoke_"))
  default = ["train", "serve"] if args.chips == 1 else ["allocation", "mesh"]
  phases = args.phase or default
  _arm_deadline(DEADLINE_S[args.chips])
  if args.rehearse:
    if args.chips > 1:
      os.environ.setdefault(
          "XLA_FLAGS",
          "--xla_force_host_platform_device_count=%d" % args.chips)
  else:
    preflight(conf)

  # what runs is what git holds: the native ring (git-ignored .so) is
  # built from native/shmring.cpp by THIS run, never taken from the disk
  from tensorflowonspark_tpu.control import shmring
  assert shmring.rebuild(), "native/shmring.cpp did not build (g++?)"
  from tensorflowonspark_tpu.utils import compile_cache
  log("compile cache: %s (%s)" % (
      compile_cache.cache_dir(),
      "JAX_COMPILATION_CACHE_DIR" if os.environ.get(
          compile_cache.ENV_JAX_CACHE_DIR) else "in-checkout default"))
  t0 = time.time()
  device = None
  for name in phases:
    log("phase %s ..." % name)
    t1 = time.time()
    rep = PHASES[name](conf)             # raises on any failure: no except
    log("phase %s passed in %.1fs: %s"
        % (name, time.time() - t1, json.dumps(rep, sort_keys=True)))
    device = rep["device"]
    if not args.rehearse:
      assert device["platform"] == "tpu", device
  assert "jax" not in sys.modules, "the parent touched JAX"
  log("all phases passed in %.1fs" % (time.time() - t0))
  if args.rehearse or phases != default:
    print(json.dumps({"rehearsal": args.rehearse, "phases": phases,
                      "device": {k: device[k] for k in
                                 ("platform", "kind", "count")}}))
    return 0
  assert device["count"] == args.chips, (device, args.chips)
  print(json.dumps({"ok": True, "device": {
      "platform": device["platform"], "kind": device["kind"],
      "count": device["count"]}}))
  return 0


if __name__ == "__main__":
  # a process group of our own, so that on a FAILED way out nothing this
  # script started is left running (a passed run has already seen every
  # process it started gone)
  try:
    os.setpgrp()
  except OSError:
    pass                                 # already a group leader
  try:
    rc = main()
  except BaseException:
    sys.stdout.flush()
    sys.stderr.write("[chip_smoke] FAILED; killed %d leftover process(es)\n"
                     % _kill_stragglers())
    raise
  sys.exit(rc)
