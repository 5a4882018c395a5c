"""Long-context Transformer training over a multi-axis mesh.

The flagship workload this framework adds beyond the reference: a
decoder-only Transformer trained with data + fsdp + sequence (ring
attention) + tensor parallelism on one jit'd train step. On a real pod the
mesh spans all chips; locally it runs on virtual CPU devices:

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  python examples/transformer/train_gpt.py --dp 2 --sp 2 --tp 2
"""

import argparse
import os
import sys

# allow running straight from a repo checkout (no install needed)
sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir)))

if __name__ == "__main__":
  parser = argparse.ArgumentParser()
  parser.add_argument("--dp", type=int, default=-1)
  parser.add_argument("--fsdp", type=int, default=1)
  parser.add_argument("--sp", type=int, default=1)
  parser.add_argument("--tp", type=int, default=1)
  parser.add_argument("--pp", type=int, default=1,
                      help="pipeline stages: >1 trains through the 1F1B "
                           "schedule (layers split into contiguous stages)")
  parser.add_argument("--microbatches", type=int, default=4)
  parser.add_argument("--layers", type=int, default=4)
  parser.add_argument("--d_model", type=int, default=256)
  parser.add_argument("--heads", type=int, default=8)
  parser.add_argument("--seq_len", type=int, default=512)
  parser.add_argument("--vocab", type=int, default=1024)
  parser.add_argument("--batch", type=int, default=8)
  parser.add_argument("--steps", type=int, default=10)
  parser.add_argument("--blocked_loss", action="store_true",
                      help="fused projection+cross-entropy (peak memory "
                           "[B,chunk,V] instead of [B,S,V])")
  parser.add_argument("--kv_heads", type=int, default=0,
                      help="grouped-query attention: 0=MHA, 1=MQA; "
                           "grouped KV rides the ring unexpanded and the "
                           "flash kernels consume it natively")
  parser.add_argument("--remat_policy", default="none",
                      choices=("none", "dots"),
                      help="'dots' saves MXU outputs at remat blocks and "
                           "recomputes only elementwise work")
  parser.add_argument("--optimizer", default="adamw",
                      choices=("adamw", "lion", "adafactor", "sgd"))
  parser.add_argument("--lr", type=float, default=3e-4)
  parser.add_argument("--grad_accum", type=int, default=1,
                      help="average gradients over k steps, update once "
                           "(effective batch = k x batch)")
  parser.add_argument("--data", default=None,
                      help="TFRecord path/glob of token rows (schema "
                           "struct<tokens:array<long>>, e.g. written by "
                           "data.dfutil.save_as_tfrecords); default: "
                           "synthetic random tokens. Streams through "
                           "readers.shard_files -> shuffled -> batched "
                           "(the FILES-mode input pipeline), with one "
                           "batch always staged ahead of the step")
  parser.add_argument("--z_loss", type=float, default=0.0,
                      help="auxiliary logit stabilizer (PaLM/T5X recipe, "
                           "e.g. 1e-4); SPMD path only")
  parser.add_argument("--unroll", type=int, default=0,
                      help="fuse K optimizer steps into one dispatch "
                           "(make_train_loop lax.scan over a [K,B,S] "
                           "slab; 0 = TOS_TRAIN_UNROLL env, default "
                           "per-step); SPMD path only")
  args = parser.parse_args()

  import time

  import numpy as np
  import jax
  import jax.numpy as jnp
  from tensorflowonspark_tpu.models import transformer as tfm
  from tensorflowonspark_tpu.parallel import mesh as M
  from tensorflowonspark_tpu.parallel import sharding as SH
  from tensorflowonspark_tpu import optim

  tx = optim.make_optimizer(learning_rate=args.lr, clip_norm=1.0,
                            optimizer=args.optimizer,
                            grad_accum_steps=args.grad_accum)

  def batch_stream():
    """[batch, seq] int32 token batches: TFRecords through the FILES-mode
    input pipeline when --data is given, else one synthetic batch."""
    if args.data:
      from tensorflowonspark_tpu.data import readers
      from tensorflowonspark_tpu.data import schema as schema_mod
      sch = schema_mod.parse_schema("struct<tokens:array<long>>")
      files = readers.shard_files(args.data, 1, 0)
      rows = readers.shuffled(
          readers.read_tfrecord_examples(files, schema=sch, repeat=True),
          buffer_size=max(64, 4 * args.batch))

      def collate(batch):
        arr = np.zeros((len(batch), args.seq_len), "int32")
        for i, r in enumerate(batch):
          t = np.asarray(r[0], "int64")[:args.seq_len]
          arr[i, :len(t)] = t
        hi = int(arr.max())
        if hi >= args.vocab:
          raise ValueError(
              "--data contains token id %d >= --vocab %d; raise --vocab "
              "to the tokenizer's size (JAX would silently clamp the "
              "embedding lookup otherwise)" % (hi, args.vocab))
        return arr

      yield from readers.batched(rows, args.batch, collate=collate)
    else:
      rng = np.random.RandomState(0)
      base = rng.randint(0, args.vocab, (args.batch, args.seq_len))
      while True:
        yield base

  def run_loop(step, state, prep):
    # one batch always staged ahead: prep (host read + async device_put /
    # shard_batch) of batch N+1 overlaps step N and stays out of the
    # timed region, so printed per-step ms measure compute, not H2D
    import collections
    stream = (prep(b) for b in batch_stream())
    buf = collections.deque()
    for i in range(args.steps):
      while len(buf) < 2:
        try:
          buf.append(next(stream))
        except StopIteration:
          break
      if not buf:
        break
      t0 = time.time()
      state, loss = step(state, buf.popleft())
      print("step %d loss %.4f (%.0f ms)"
            % (i, float(loss), 1000 * (time.time() - t0)))
    print("done; tokens/step = %d" % (args.batch * args.seq_len))

  if args.pp > 1:
    # 1F1B pipeline path: DP x PP mesh, blocks split into contiguous
    # stages, constant activation memory in the microbatch count
    if args.fsdp > 1 or args.sp > 1 or args.tp > 1 or args.blocked_loss \
        or args.z_loss:
      parser.error("--pp composes with --dp only (--fsdp/--sp/--tp/"
                   "--blocked_loss/--z_loss are the SPMD path)")
    if args.dp == -1:
      args.dp = max(1, len(jax.devices()) // args.pp)
    micro_b = args.batch // args.microbatches
    if args.batch % args.microbatches or micro_b % args.dp:
      parser.error(
          "batch %d must split into %d microbatches divisible by dp=%d "
          "(e.g. --batch %d)" % (args.batch, args.microbatches, args.dp,
                                 args.microbatches * args.dp))
    mesh = M.build_mesh(M.MeshSpec(data=args.dp, pipeline=args.pp))
    print("mesh:", dict(mesh.shape))
    cfg = tfm.TransformerConfig(
        vocab_size=args.vocab, num_layers=args.layers,
        num_heads=args.heads, d_model=args.d_model,
        d_ff=args.d_model * 4, max_seq_len=args.seq_len,
        num_kv_heads=args.kv_heads, remat_policy=args.remat_policy)
    state = tfm.create_state(jax.random.PRNGKey(0), cfg,
                             seq_len=args.seq_len, tx=tx)
    pipe = tfm.make_pipeline_train_step(cfg, mesh, args.microbatches)

    @jax.jit
    def pp_step(state, tokens):
      loss, grads = pipe(state.params, tokens)
      return state.apply_gradients(grads=grads), loss

    run_loop(pp_step, state, lambda b: jnp.asarray(b, jnp.int32))
    sys.exit(0)

  mesh = M.build_mesh(M.MeshSpec(data=args.dp, fsdp=args.fsdp,
                                 sequence=args.sp, tensor=args.tp))
  print("mesh:", dict(mesh.shape))

  cfg = tfm.TransformerConfig(
      vocab_size=args.vocab, num_layers=args.layers, num_heads=args.heads,
      d_model=args.d_model, d_ff=args.d_model * 4,
      max_seq_len=args.seq_len, num_kv_heads=args.kv_heads,
      remat_policy=args.remat_policy,
      use_ring_attention=mesh.shape[M.AXIS_SEQUENCE] > 1)
  state, sharding = tfm.create_sharded_state(jax.random.PRNGKey(0), cfg,
                                             mesh, seq_len=args.seq_len,
                                             tx=tx)

  def loss_fn(params, tokens):
    if args.blocked_loss:
      # fused projection+xent: never materializes [batch, seq, vocab]
      hidden = state.apply_fn({"params": params}, tokens,
                              return_hidden=True)
      return tfm.causal_lm_loss_blocked(
          hidden, tfm.tied_embedding_table(params), tokens,
          z_loss=args.z_loss)
    return tfm.causal_lm_loss(state.apply_fn({"params": params}, tokens),
                              tokens, z_loss=args.z_loss)

  unroll = SH.resolve_unroll(args.unroll or None)
  if unroll > 1:
    # fused multi-step path: K batches stacked into one Slab, K steps
    # per dispatch, the [K] loss vector fetched once per slab — same
    # trajectory as per-step (docs/PERFORMANCE.md §Train-loop fusion)
    import itertools
    from tensorflowonspark_tpu.data.readers import Slab
    loop = SH.make_train_loop(loss_fn, mesh, sharding,
                              batch_extra_axes=(M.AXIS_SEQUENCE,),
                              unroll=unroll)
    stream = batch_stream()
    while loop.steps < args.steps:
      group = [np.asarray(b, "int32") for b in
               itertools.islice(stream, min(unroll,
                                            args.steps - loop.steps))]
      if not group:
        break
      t0 = time.time()
      # a short tail group still rides the loop (per-step jit entry)
      state, losses = loop(state, Slab(np.stack(group)))
      losses = np.asarray(losses)
      print("steps %d..%d mean loss %.4f (%.0f ms, %d step(s)/dispatch)"
            % (loop.steps - len(group), loop.steps - 1, losses.mean(),
               1000 * (time.time() - t0), len(group)))
    print("done; tokens/step = %d" % (args.batch * args.seq_len))
    sys.exit(0)

  step = SH.make_train_step(loss_fn, mesh, sharding,
                            batch_extra_axes=(M.AXIS_SEQUENCE,))

  run_loop(step, state,
           lambda b: SH.shard_batch(jnp.asarray(b, jnp.int32), mesh,
                                    extra_axes=(M.AXIS_SEQUENCE,)))
