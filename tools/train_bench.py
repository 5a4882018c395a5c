"""Per-step vs fused train loop: what does dispatch amortization buy?

The per-step training path (`parallel.sharding.make_train_step`) pays
one host dispatch, one host→device batch transfer and one metrics sync
per optimizer step; at dispatch-dominated step times that overhead is
the step time (the serving bench proved the same effect on the decode
side — fusing a horizon bought 1.78x). This bench runs the SAME batches
through both paths of `parallel.sharding.make_train_loop`:

- ``per-step``: one ``loop(state, batch)`` dispatch per optimizer step,
  loss harvested per step — the status-quo loop every example runs
  (StepTimer semantics: block on the loss inside the step region);
- ``fused``: ``unroll`` batches stacked into one ``data.readers.Slab``,
  one jitted ``lax.scan`` dispatch per slab, the ``[unroll]`` loss
  vector harvested once per slab.

Both paths pay their host→device transfer per dispatch (one device_put
per batch vs one per slab) — the three per-step costs the fusion
amortizes. Data is pre-staged host-side so the feed plane stays out of
the measurement (feed overhead is `feed_bench`'s job); batches are
DISTINCT so the loss trajectory moves, and the bench asserts the fused
trajectory is BIT-IDENTICAL to the per-step one on every rep — the
fusion contract, re-verified on each run.

Methodology (feed_bench/serve_bench house rules): PAIRED reps — each
rep times per-step then fused back to back so this box's CPU throttling
hits both sides of a ratio equally; the headline is the MEDIAN rep's
speedup; core pinning keeps XLA on one core. Prints ONE JSON line;
``--json-out`` additionally writes it to a file and appends a
``train_bench`` series line to ``bench_artifacts/history.jsonl``.

``--groups N`` switches to the elastic-groups bench
(`parallel.groups.GroupSet`): each paired rep runs N groups WITHOUT
cross-group sync (``sync_every=0``) then N groups syncing every
``--unroll`` steps — same thread count and same compute on both sides,
so the ratio isolates what the sync plane (pack + wire + weighted merge
+ poll) costs per step. Both sides pay per-group compile inside the
timed window — paired, so it dilutes (never inflates) the measured
overhead. The synced side also re-verifies interchangeability: after
the final boundary every group's params must be bit-identical.

Usage:  python tools/train_bench.py [--steps 320] [--batch 16]
                                    [--unroll 8] [--reps 3] [--smoke]
                                    [--groups N] [--json-out PATH]
"""

import argparse
import json
import os
import sys
import time
from statistics import median as _median

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tensorflowonspark_tpu.obs import metrics as obs_metrics  # noqa: E402
from tools.feed_bench import _pin_to_core  # noqa: E402 - one pin impl


def _build(hidden: int, batch: int, unroll: int, steps: int, seed: int = 0):
  """The dispatch-dominated harness: a small MLP train step + pre-staged
  host batches (distinct per step, shared by both paths)."""
  import numpy as np
  import jax
  import jax.numpy as jnp
  import optax
  from flax import linen as nn
  from flax.training import train_state
  from tensorflowonspark_tpu.data.readers import Slab
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib

  class MLP(nn.Module):
    @nn.compact
    def __call__(self, x):
      x = nn.Dense(hidden)(x)
      x = nn.relu(x)
      return nn.Dense(10)(x)

  model = MLP()
  params0 = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, 784)))["params"]

  def fresh_state():
    # the fused path donates its state: every run needs its own copies
    params = jax.tree.map(jnp.array, params0)
    return train_state.TrainState.create(apply_fn=model.apply,
                                         params=params, tx=optax.sgd(0.01))

  def loss_fn(p, b):
    logits = model.apply({"params": p}, b["x"])
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, b["y"]).mean()

  rng = np.random.RandomState(seed)
  batches = [{"x": rng.rand(batch, 784).astype("float32"),
              "y": rng.randint(0, 10, batch).astype("int32")}
             for _ in range(steps)]
  slabs = [Slab({k: np.stack([batches[i + j][k] for j in range(unroll)])
                 for k in ("x", "y")})
           for i in range(0, steps, unroll)]
  # one device regardless of XLA_FLAGS device-count overrides: the bench
  # measures dispatch amortization, not cross-device collectives
  mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=-1),
                             devices=jax.devices()[:1])
  return fresh_state, loss_fn, mesh, batches, slabs


def _run_path(loop, fresh_state, items, per: int):
  """Time one path; returns (steps/sec, loss trajectory as a list).

  Every dispatch pays its own host→device transfer (device_put of the
  host batch/slab) and its own loss harvest (block_until_ready) — the
  per-step status quo semantics on both sides, so the ratio isolates
  what fusing K dispatches into one buys.
  """
  import numpy as np
  import jax
  state = fresh_state()
  # warmup: compile outside the timed window
  state, losses = loop(state, jax.device_put(items[0]))
  jax.block_until_ready(losses)
  state = fresh_state()
  traj = []
  n = 0
  t0 = time.perf_counter()
  for item in items:
    state, losses = loop(state, jax.device_put(item))
    traj.append(np.asarray(losses))
    n += per
  dt = time.perf_counter() - t0
  return n / dt, [float(v) for arr in traj for v in arr.reshape(-1)]


def run_pair(hidden, batch, unroll, steps):
  """One paired rep: per-step then fused over the SAME batches."""
  from tensorflowonspark_tpu.parallel import sharding as SH

  fresh_state, loss_fn, mesh, batches, slabs = _build(hidden, batch,
                                                      unroll, steps)
  loop1 = SH.make_train_loop(loss_fn, mesh, unroll=1, donate_state=True)
  loopk = SH.make_train_loop(loss_fn, mesh, unroll=unroll,
                             donate_state=True)
  rate1, traj1 = _run_path(loop1, fresh_state, batches, 1)
  ratek, trajk = _run_path(loopk, fresh_state, slabs, unroll)
  return rate1, ratek, traj1 == trajk


def _groups_harness(hidden: int, batch: int, seed: int = 0):
  """``build_fn``/``batch_fn`` pair for the GroupSet bench: the same MLP
  as the fusion bench, per-group deterministic data keyed by
  ``(group_id, step)`` (the GroupSet data-position contract)."""
  import numpy as np
  import jax
  import jax.numpy as jnp
  import optax
  from flax import linen as nn
  from flax.training import train_state

  class MLP(nn.Module):
    @nn.compact
    def __call__(self, x):
      x = nn.Dense(hidden)(x)
      x = nn.relu(x)
      return nn.Dense(10)(x)

  model = MLP()
  params0 = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, 784)))["params"]

  def build_fn(mesh):
    del mesh  # single-device groups: the loop handles placement
    params = jax.tree.map(jnp.array, params0)
    state = train_state.TrainState.create(apply_fn=model.apply,
                                          params=params, tx=optax.sgd(0.01))

    def loss_fn(p, b):
      logits = model.apply({"params": p}, b["x"])
      return optax.softmax_cross_entropy_with_integer_labels(
          logits, b["y"]).mean()

    return state, loss_fn

  def batch_fn(group_id, step):
    rng = np.random.RandomState(seed + 7919 * group_id + step)
    return {"x": rng.rand(batch, 784).astype("float32"),
            "y": rng.randint(0, 10, batch).astype("int32")}

  return build_fn, batch_fn


def run_groups_pair(hidden, batch, num_groups, sync_every, steps):
  """One paired rep: N groups no-sync, then N groups syncing every
  ``sync_every`` steps. Returns (nosync steps/s, synced steps/s,
  plane status, params-identical-after-final-sync)."""
  from tensorflowonspark_tpu.parallel import groups as G

  def timed(se):
    build_fn, batch_fn = _groups_harness(hidden, batch)
    gs = G.GroupSet(build_fn, batch_fn, num_groups=num_groups,
                    sync_every=se, sync_timeout=30.0)
    try:
      t0 = time.perf_counter()
      gs.run(steps)
      if not gs.wait(timeout=600.0):
        raise RuntimeError("group threads did not finish within 600s")
      dt = time.perf_counter() - t0
      stuck = [g.group_id for g in gs.groups.values()
               if g.exit_reason != "completed"]
      if stuck:
        raise RuntimeError("group(s) %s did not complete cleanly" % stuck)
      status = gs.plane.status()
      packed = [G.pack_tree(g.state.params) for g in gs.groups.values()]
      identical = all(
          all(a["data"] == b["data"] for a, b in zip(packed[0], p))
          for p in packed[1:])
      return num_groups * steps / dt, status, identical
    finally:
      gs.close()

  rate0, _, _ = timed(0)
  rate1, status, identical = timed(sync_every)
  return rate0, rate1, status, identical


def run_groups_main(args):
  """The ``--groups`` entry point: paired no-sync vs synced reps."""
  nosync, synced, overheads = [], [], []
  identical = True
  status = {}
  for _ in range(max(1, args.reps)):
    r0, r1, status, ident = run_groups_pair(
        args.hidden, args.batch, args.groups, args.unroll, args.steps)
    nosync.append(r0)
    synced.append(r1)
    overheads.append((r0 / r1 - 1.0) * 100.0)
    identical = identical and ident

  result = {
      "metric": "train_groups_sync_overhead",
      "groups": args.groups,
      "sync_every": args.unroll,
      "overhead_pct_median": round(_median(overheads), 2),
      "overhead_pct_reps": [round(o, 2) for o in overheads],
      "nosync_steps_per_sec": round(_median(nosync), 2),
      "synced_steps_per_sec": round(_median(synced), 2),
      "sync_rounds": status.get("rounds_completed"),
      "last_sync_ms": status.get("sync_ms"),
      "params_identical_after_sync": identical,
      "batch": args.batch,
      "hidden": args.hidden,
      "steps": args.steps,
      "reps": args.reps,
      "obs": int(obs_metrics.enabled()),
      "note": "overhead = extra wall per optimizer step the cross-group "
              "sync plane costs vs the same N groups with sync disabled, "
              "per PAIRED rep, median rep reported; compile time rides "
              "both sides (dilutes, never inflates); "
              "params_identical_after_sync re-verifies group "
              "interchangeability at the final boundary.",
  }
  line = json.dumps(result)
  print(line)
  if not identical:
    sys.stderr.write("GROUP PARAMS DIVERGED AFTER FINAL SYNC\n")
    return 1
  if args.json_out:
    with open(args.json_out, "w") as f:
      f.write(line + "\n")
    from tools import bench_history
    bench_history.append_record(
        "train_bench_groups", result["overhead_pct_median"],
        "g%d-e%d-b%d-h%d-s%d" % (args.groups, args.unroll, args.batch,
                                 args.hidden, args.steps),
        extra={"synced_steps_per_sec": result["synced_steps_per_sec"],
               "nosync_steps_per_sec": result["nosync_steps_per_sec"],
               "obs": result["obs"]})
  return 0


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument("--steps", type=int, default=320,
                  help="optimizer steps per timed run (multiple of unroll)")
  ap.add_argument("--batch", type=int, default=16)
  ap.add_argument("--hidden", type=int, default=128)
  ap.add_argument("--unroll", type=int, default=8,
                  help="fused steps per dispatch (the K under test)")
  ap.add_argument("--reps", type=int, default=3,
                  help="paired repetitions (median rep reported)")
  ap.add_argument("--groups", type=int, default=0, metavar="N",
                  help="elastic-groups mode: cross-group sync overhead "
                       "with N groups syncing every --unroll steps "
                       "(0 = fusion bench)")
  ap.add_argument("--smoke", action="store_true",
                  help="tiny run (CPU CI / plumbing check)")
  ap.add_argument("--json-out", default=None,
                  help="additionally write the JSON result to this path")
  args = ap.parse_args()
  from tensorflowonspark_tpu.utils import compile_cache
  compile_cache.setup()                  # this process jits: place the cache
  if args.smoke or os.environ.get("TOS_BENCH_SMOKE"):
    args.steps, args.batch, args.hidden, args.reps = 32, 16, 64, 1
  if args.steps % args.unroll:
    args.steps += args.unroll - args.steps % args.unroll
  _pin_to_core(0)   # before jax's first use so XLA threads inherit it
  if obs_metrics.enabled():
    # price the device tier exactly like an obs-enabled cluster process
    from tensorflowonspark_tpu.obs import device as obs_device
    obs_device.install_compile_listener()
  if args.groups:
    return run_groups_main(args)

  per_step, fused, speedups = [], [], []
  parity = True
  for _ in range(max(1, args.reps)):
    r1, rk, bit_identical = run_pair(args.hidden, args.batch, args.unroll,
                                     args.steps)
    per_step.append(r1)
    fused.append(rk)
    speedups.append(rk / r1)
    parity = parity and bit_identical

  result = {
      "metric": "train_fused_speedup",
      "speedup_median": round(_median(speedups), 3),
      "speedup_reps": [round(s, 3) for s in speedups],
      "per_step_steps_per_sec": round(_median(per_step), 2),
      "fused_steps_per_sec": round(_median(fused), 2),
      "losses_bit_identical": parity,
      "unroll": args.unroll,
      "batch": args.batch,
      "hidden": args.hidden,
      "steps": args.steps,
      "reps": args.reps,
      "obs": int(obs_metrics.enabled()),
      "note": "speedup = fused/per-step steps/s per PAIRED rep, median "
              "rep reported; both paths pay per-dispatch device_put + "
              "loss harvest; losses_bit_identical re-verifies the fusion "
              "contract (same batches => same trajectory, bitwise) on "
              "every rep.",
  }
  line = json.dumps(result)
  print(line)
  if not parity:
    sys.stderr.write("FUSED TRAJECTORY DIVERGED FROM PER-STEP\n")
    return 1
  if args.json_out:
    with open(args.json_out, "w") as f:
      f.write(line + "\n")
    from tools import bench_history
    bench_history.append_record(
        "train_bench", result["fused_steps_per_sec"],
        "u%d-b%d-h%d-s%d" % (args.unroll, args.batch, args.hidden,
                             args.steps),
        extra={"speedup": result["speedup_median"],
               "obs": result["obs"]})
  return 0


if __name__ == "__main__":
  sys.exit(main())
