"""Weak-scaling dry-run benchmark on a virtual CPU mesh.

The real environment exposes ONE TPU chip, so multi-chip scaling cannot be
measured for real; what CAN be validated on one host is that the sharded
training step's collective structure scales — per-device work stays constant
as devices double (weak scaling: global batch grows with the mesh) and the
XLA-inserted gradient allreduce doesn't blow up step time. Each mesh size
runs in its own subprocess (the CPU device count is fixed at backend init),
training the same per-device-batch Transformer data-parallel.

CPU wall-clock is NOT a TPU throughput prediction — the number that matters
is the parallel efficiency column (t_1 / t_n for constant per-device work;
1.0 is perfect). Results land in stdout as JSON lines.

Usage: python tools/weak_scaling.py            # parent: runs 1,2,4,8
       python tools/weak_scaling.py --child N  # one mesh size (internal)
"""

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PER_DEVICE_BATCH = 4
SEQ = 128
STEPS = 8


def run_child(n_devices: int) -> int:
  sys.path.insert(0, REPO)
  from tensorflowonspark_tpu.utils.platform_env import force_cpu_platform
  force_cpu_platform(n_devices)

  import jax
  import jax.numpy as jnp
  import numpy as np
  from tensorflowonspark_tpu.models import transformer as tfm
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib
  from tensorflowonspark_tpu.parallel import sharding as sh

  # an inherited XLA_FLAGS may pin a LARGER device count than requested
  # (force_cpu_platform preserves it); take the first n rather than fail
  assert len(jax.devices()) >= n_devices, \
      "need %d devices, have %d" % (n_devices, len(jax.devices()))
  mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=n_devices),
                             devices=jax.devices()[:n_devices])
  cfg = tfm.TransformerConfig(vocab_size=256, num_layers=2, num_heads=4,
                              d_model=128, d_ff=512, max_seq_len=SEQ,
                              dtype=jnp.float32)
  state, state_sharding = tfm.create_sharded_state(
      jax.random.PRNGKey(0), cfg, mesh, seq_len=SEQ)

  def loss_fn(params, tokens):
    return tfm.causal_lm_loss(
        state.apply_fn({"params": params}, tokens), tokens)

  step = sh.make_train_step(loss_fn, mesh, state_sharding)
  batch = n_devices * PER_DEVICE_BATCH          # weak scaling
  rng = np.random.RandomState(0)
  tokens = sh.shard_batch(
      jnp.asarray(rng.randint(0, 256, (batch, SEQ)), jnp.int32), mesh)

  state, loss = step(state, tokens)             # compile
  jax.block_until_ready(loss)
  t0 = time.time()
  for _ in range(STEPS):
    state, loss = step(state, tokens)
  jax.block_until_ready(loss)
  dt = (time.time() - t0) / STEPS
  print(json.dumps({"devices": n_devices, "global_batch": batch,
                    "step_ms": round(dt * 1e3, 1),
                    "loss": round(float(loss), 4)}))
  return 0


def main(argv=None):
  ap = argparse.ArgumentParser()
  ap.add_argument("--child", type=int, default=None)
  ap.add_argument("--sizes", default="1,2,4,8")
  args = ap.parse_args(argv)
  if args.child is not None:
    return run_child(args.child)

  rows = []
  failed = False
  for n in [int(s) for s in args.sizes.split(",")]:
    env = dict(os.environ)
    try:
      proc = subprocess.run(
          [sys.executable, os.path.abspath(__file__), "--child", str(n)],
          capture_output=True, text=True, env=env, cwd=REPO, timeout=900)
    except subprocess.TimeoutExpired:
      print(json.dumps({"devices": n, "error": "child timed out (900s)"}))
      failed = True
      continue
    out = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    if proc.returncode != 0 or not out:
      print(json.dumps({"devices": n, "error":
                        (proc.stderr or proc.stdout)[-300:]}))
      failed = True
      continue
    rows.append(json.loads(out[-1]))
    print(out[-1])

  if rows:
    # virtual CPU devices SHARE the host's cores: with n devices on c
    # cores the hardware can at best run min(n, c) device programs at
    # once, so per-device serialization inflates a step by
    # norm(n) = n / min(n, c). The ideal weak-scaled step time relative
    # to the SMALLEST measured mesh n0 is t_n0 * norm(n) / norm(n0);
    # efficiency vs that ideal isolates what this proxy can actually
    # measure — whether the XLA-inserted gradient collectives add
    # superlinear overhead as the mesh grows (~1.0 = the sharded step
    # structure scales).
    cores = len(os.sched_getaffinity(0))
    norm = lambda n: n / min(n, cores)           # noqa: E731
    n0, base = rows[0]["devices"], rows[0]["step_ms"]
    print("\nweak scaling (per-device batch=%d, %d host core(s)):"
          % (PER_DEVICE_BATCH, cores), file=sys.stderr)
    for r in rows:
      n = r["devices"]
      ideal = base * norm(n) / norm(n0)
      eff = ideal / r["step_ms"]
      print("  %d device(s): global_batch=%d step=%.1fms "
            "collective-efficiency=%.2f" % (n, r["global_batch"],
                                            r["step_ms"], eff),
            file=sys.stderr)
  return 1 if failed else 0


if __name__ == "__main__":
  sys.exit(main())
