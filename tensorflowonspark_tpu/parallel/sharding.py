"""Sharding rules + the SPMD train-step factory.

This is the TPU-native analog of the reference wiring a
``MultiWorkerMirroredStrategy`` from TF_CONFIG (e.g. reference
examples/mnist/keras/mnist_spark.py:11): one call produces a jitted train
step whose parameters and batch are laid out over the mesh, with gradient
all-reduce (DP), parameter sharding (TP/FSDP) and activation sharding
compiled by XLA into ICI collectives.

Parameter placement uses flax logical-axis rules: modules annotate
``nn.with_partitioning`` / logical names, and ``LOGICAL_RULES`` maps those
names onto mesh axes.
"""

import logging
import os
import time
from typing import Callable, Optional, Tuple

from tensorflowonspark_tpu.obs import device as obs_device
from tensorflowonspark_tpu.obs import metrics as obs_metrics
from tensorflowonspark_tpu.obs import spans as obs_spans
from tensorflowonspark_tpu.parallel import mesh as mesh_lib

logger = logging.getLogger(__name__)

#: default unroll for :func:`make_train_loop` — how many optimizer steps
#: one dispatch fuses. 1 = the status-quo per-step path. Set by
#: ``cluster.run(train_unroll=K)`` on every node (env registry: TOS008)
ENV_TRAIN_UNROLL = "TOS_TRAIN_UNROLL"


def resolve_unroll(unroll: Optional[int] = None) -> int:
  """The effective train-loop unroll: explicit argument beats the
  ``TOS_TRAIN_UNROLL`` env (which ``cluster.run(train_unroll=K)`` exports
  into every node process); default 1 — the per-step status quo.

  Env values that don't name a usable K (malformed, empty, ``0`` — the
  CLI convention for "per-step") resolve to 1 rather than raising: an
  env typo must not crash every node's main fn. An EXPLICIT ``unroll``
  argument < 1 is a caller bug and raises.
  """
  if unroll is None:
    try:
      unroll = int(os.environ.get(ENV_TRAIN_UNROLL, "1"))
    except ValueError:
      unroll = 1
    return max(1, unroll)
  if unroll < 1:
    raise ValueError("train unroll must be >= 1, got %d" % unroll)
  return int(unroll)

# logical axis name -> mesh axis (None = replicated)
LOGICAL_RULES = (
    ("batch", (mesh_lib.AXIS_DATA, mesh_lib.AXIS_FSDP)),
    ("sequence", mesh_lib.AXIS_SEQUENCE),
    ("vocab", mesh_lib.AXIS_TENSOR),
    ("embed", mesh_lib.AXIS_FSDP),
    ("heads", mesh_lib.AXIS_TENSOR),
    ("kv", None),
    ("mlp", mesh_lib.AXIS_TENSOR),
    ("stage", mesh_lib.AXIS_PIPELINE),
    ("expert", mesh_lib.AXIS_EXPERT),
    ("conv_in", None),
    ("conv_out", mesh_lib.AXIS_TENSOR),
)


def batch_sharding(mesh, extra_axes: Tuple[str, ...] = ()):
  """NamedSharding placing dim 0 of a batch over the data(/fsdp) axes and,
  optionally, dim 1 over the sequence axis."""
  from jax.sharding import NamedSharding, PartitionSpec as P
  dims = [mesh_lib.data_axes(mesh) or None]
  dims.extend(extra_axes)
  return NamedSharding(mesh, P(*dims))


def replicated(mesh):
  from jax.sharding import NamedSharding, PartitionSpec as P
  return NamedSharding(mesh, P())


def logical_to_mesh_sharding(logical_specs, mesh):
  """Apply LOGICAL_RULES to a pytree of flax logical PartitionSpecs."""
  import flax.linen as nn
  return nn.logical_to_mesh_sharding(logical_specs, mesh,
                                     rules=LOGICAL_RULES)


def param_sharding_from_boxed(boxed_params, mesh):
  """Sharding tree from flax ``Partitioned``-boxed params (as returned by
  ``model.init`` when modules use ``with_logical_partitioning``)."""
  import jax
  import flax.linen as nn
  from jax.sharding import NamedSharding, PartitionSpec as P

  logical = nn.get_partition_spec(boxed_params)
  shardings = logical_to_mesh_sharding(logical, mesh)

  def _fix(leaf):
    return leaf if isinstance(leaf, NamedSharding) else NamedSharding(mesh, P())

  return jax.tree.map(_fix, shardings,
                      is_leaf=lambda x: isinstance(x, NamedSharding)
                      or x is None)


def state_shardings(abs_state, param_sharding, mesh):
  """Shardings for a whole TrainState: params exact, optimizer moments
  mirror THEIR parameter (matched by tree path, so two same-shaped params
  with different layouts keep their own moment layouts — a shape-keyed
  lookup would silently reshard one of them every step), everything else
  replicated."""
  import jax
  from jax.sharding import NamedSharding, PartitionSpec as P
  from jax.tree_util import tree_flatten_with_path, tree_unflatten

  def _names(path):
    return tuple(str(getattr(k, "key", getattr(k, "name",
                                               getattr(k, "idx", k))))
                 for k in path)

  param_flat, _ = tree_flatten_with_path(abs_state.params)
  by_path = {}
  for (path, leaf), sh in zip(param_flat, jax.tree.leaves(param_sharding)):
    by_path[_names(path)] = (tuple(leaf.shape), sh)

  state_flat, treedef = tree_flatten_with_path(abs_state)
  out = []
  for path, leaf in state_flat:
    names = _names(path)
    sh = None
    if getattr(leaf, "ndim", 0) > 0:
      # optimizer moments live at <state prefix> + <param path>: take the
      # longest path suffix that names a parameter of the same shape
      for i in range(len(names)):
        hit = by_path.get(names[i:])
        if hit is not None and hit[0] == tuple(getattr(leaf, "shape", ())):
          sh = hit[1]
          break
    out.append(sh if sh is not None else NamedSharding(mesh, P()))
  full = tree_unflatten(treedef, out)
  return full.replace(params=param_sharding)


def init_sharded_state(params_init_fn: Callable, make_state_fn: Callable,
                       mesh):
  """Initialize a TrainState directly sharded over ``mesh``.

  ``params_init_fn()`` returns flax ``model.init(...)``'s (possibly
  Partitioned-boxed) params; ``make_state_fn(unboxed_params)`` wraps them in
  a TrainState (running the optimizer init). Uses eval_shape +
  jit(out_shardings=...) so even the initializers run sharded — parameters
  larger than one host's memory never materialize unsharded.

  Returns (state, state_sharding).
  """
  import jax
  from flax.core import meta

  def _full_init():
    return make_state_fn(meta.unbox(params_init_fn()))

  abs_boxed = jax.eval_shape(params_init_fn)
  param_sharding = param_sharding_from_boxed(abs_boxed, mesh)
  abs_state = jax.eval_shape(_full_init)
  sharding = state_shardings(abs_state, param_sharding, mesh)
  state = jax.jit(_full_init, out_shardings=sharding)()
  return state, sharding


def make_train_step(loss_fn: Callable,
                    mesh,
                    state_sharding=None,
                    donate_state: bool = True,
                    batch_extra_axes: Tuple[str, ...] = ()):
  """Build a jitted SPMD train step: ``step(state, batch) -> (state, loss)``.

  ``loss_fn(params, batch)`` must be pure. The batch is sharded over
  data/fsdp (plus ``batch_extra_axes``, e.g. ("sequence",) for
  sequence-parallel inputs); parameters/optimizer follow ``state_sharding``
  (from :func:`init_sharded_state`) or are replicated when None. XLA compiles
  the gradient sync to ICI collectives.
  """
  import jax

  batch_shard = batch_sharding(mesh, batch_extra_axes)

  def _step(state, batch):
    # recompile sentinel seam (obs/device.py): a steady-state train loop
    # must never re-trace this — pinned by the recompile-sentinel test
    obs_device.note_trace("train.step")
    loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
    return state.apply_gradients(grads=grads), loss

  kw = {}
  if state_sharding is not None:
    kw = dict(in_shardings=(state_sharding, batch_shard),
              out_shardings=(state_sharding, replicated(mesh)))
  step = jax.jit(_step, donate_argnums=(0,) if donate_state else (), **kw)
  if not obs_device.device_tier_enabled():
    return step

  # device tier on: capture the train step's HLO cost (flops / bytes
  # accessed) at first call. The wrapper adds one dict check per step and
  # keeps the jit's AOT surface (.lower) for mosaic_gate-style callers.
  pending = {"capture": True}

  def step_with_cost(state, batch):
    if pending["capture"]:
      pending["capture"] = False
      obs_device.capture_cost("train.step", step, state, batch)
    return step(state, batch)

  step_with_cost.lower = step.lower
  return step_with_cost


def slab_sharding(mesh, extra_axes: Tuple[str, ...] = ()):
  """NamedSharding for a ``[K, B, ...]`` batch slab: the leading (scan)
  dim replicated, dim 1 over data/fsdp (plus ``extra_axes`` from dim 2)
  — the slab analog of :func:`batch_sharding`."""
  from jax.sharding import NamedSharding, PartitionSpec as P
  dims = [None, mesh_lib.data_axes(mesh) or None]
  dims.extend(extra_axes)
  return NamedSharding(mesh, P(*dims))


class TrainLoop(object):
  """Callable built by :func:`make_train_loop`: per-step and fused paths
  behind one dispatch surface.

  ``loop(state, item) -> (state, losses)`` where ``item`` is either a
  plain batch (one optimizer step; ``losses`` has shape ``[1]``) or a
  :class:`data.readers.Slab` of ``unroll`` stacked batches (one fused
  ``lax.scan`` dispatch; ``losses`` has shape ``[unroll]``, reduced on
  device and fetched once per slab). ``loop.steps`` counts optimizer
  steps taken host-side — the step-accurate value to hand to
  ``CheckpointManager.save`` at slab boundaries.
  """

  def __init__(self, step_fn, fused_fn, unroll: int, obs_handles):
    self._step = step_fn
    self._fused = fused_fn
    self.unroll = unroll
    #: optimizer steps dispatched through this loop (host-side count)
    self.steps = 0
    self._obs = obs_handles      # None, or (counter, recorder-or-None)

  def _record(self, n: int, t0: float) -> None:
    self.steps += n
    if self._obs is None:
      return
    counter, rec = self._obs
    counter.inc(n)
    if rec is not None:
      rec.record_span("train.slab", t0, time.monotonic() - t0, steps=n)

  @staticmethod
  def _unstack(slab_data):
    import jax
    leaves = jax.tree.leaves(slab_data)
    n = leaves[0].shape[0] if leaves else 0
    return [jax.tree.map(lambda x, i=i: x[i], slab_data) for i in range(n)]

  def _per_step(self, state, batches, t0: float):
    import jax.numpy as jnp
    losses = []
    for batch in batches:
      state, loss = self._step(state, batch)
      losses.append(loss)
    self._record(len(losses), t0)
    return state, jnp.stack(losses) if losses else jnp.zeros((0,))

  def lower(self, state, item):
    """AOT-lower the program ``loop(state, item)`` would dispatch (the
    fused scan for a full :class:`Slab`, else the per-step entry) — for
    ``.compile()``'s ``as_text()``/``memory_analysis()`` and for the
    deviceless compile gate, where ``state``/``item`` may be abstract."""
    import jax
    from tensorflowonspark_tpu.data.readers import Slab
    if isinstance(item, Slab):
      leaves = jax.tree.leaves(item.data)
      if self._fused is not None and leaves \
          and leaves[0].shape[0] == self.unroll:
        return self._fused.lower(state, item.data)
      raise ValueError("only a full [unroll=%d, ...] slab has a fused "
                       "program to lower" % self.unroll)
    return self._step.lower(state, item)

  def __call__(self, state, item):
    from tensorflowonspark_tpu.data.readers import Slab
    t0 = time.monotonic()
    if isinstance(item, Slab):
      import jax
      leaves = jax.tree.leaves(item.data)
      k = leaves[0].shape[0] if leaves else 0
      if self._fused is not None and k == self.unroll:
        state, losses = self._fused(state, item.data)
        self._record(self.unroll, t0)
        return state, losses
      # a slab that doesn't match the fused shape (partial tail that was
      # stacked anyway, or unroll=1): the per-step jit entry serves it
      return self._per_step(state, self._unstack(item.data), t0)
    return self._per_step(state, [item], t0)


def make_train_loop(loss_fn: Callable,
                    mesh,
                    state_sharding=None,
                    donate_state: bool = True,
                    batch_extra_axes: Tuple[str, ...] = (),
                    unroll: Optional[int] = None) -> TrainLoop:
  """Build a dispatch-amortized train loop: ``unroll`` optimizer steps
  fused into one jitted ``lax.scan`` over a ``[unroll, B, ...]`` slab.

  The per-step path (``make_train_step``) pays one host dispatch, one
  host→device transfer and one metrics sync per optimizer step; at small
  step times that overhead dominates (the serving side proved the same
  amortization with its decode horizon). The fused path scans the SAME
  step body over a slab of ``unroll`` stacked batches with the state
  donated, so K steps ride one dispatch and the ``[unroll]`` loss vector
  is fetched once per slab.

  Contract (pinned by tests): same batch order in ⇒ bit-identical
  loss/param trajectory vs the per-step path — ``optax.MultiSteps``
  grad-accum included (``state.tx`` is applied once per scanned step,
  exactly as the per-step path applies it). The jit cache stays at
  exactly two entries: the fused ``[unroll, B, ...]`` scan and the
  ``[B, ...]`` per-step fallback that partial final slabs ride.

  ``unroll=None`` reads ``TOS_TRAIN_UNROLL`` (exported into every node
  by ``cluster.run(train_unroll=K)``); 1 keeps the per-step status quo
  with the same calling convention. Feed slabs with
  ``data.readers.slab_batches(feed, B, unroll)`` composed with
  ``device_prefetch`` so slab k+1 transfers under slab k's compute.
  """
  import jax
  from jax import lax

  unroll = resolve_unroll(unroll)
  step = make_train_step(loss_fn, mesh, state_sharding,
                         donate_state=donate_state,
                         batch_extra_axes=batch_extra_axes)

  fused = None
  if unroll > 1:
    slab_shard = slab_sharding(mesh, batch_extra_axes)

    def _loop(state, slab):
      # recompile sentinel seam: a steady-state fused loop must never
      # re-trace this (obs/device.py; same pin as the per-step seam)
      obs_device.note_trace("train.loop")

      def body(state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        return state.apply_gradients(grads=grads), loss

      return lax.scan(body, state, slab)

    kw = {}
    if state_sharding is not None:
      kw = dict(in_shardings=(state_sharding, slab_shard),
                out_shardings=(state_sharding, replicated(mesh)))
    fused = jax.jit(_loop, donate_argnums=(0,) if donate_state else (),
                    **kw)
    if obs_device.device_tier_enabled():
      inner, pending = fused, {"capture": True}

      def fused_with_cost(state, slab):
        if pending["capture"]:
          pending["capture"] = False
          obs_device.capture_cost("train.loop", inner, state, slab)
        return inner(state, slab)

      fused_with_cost.lower = inner.lower
      fused = fused_with_cost

  obs_handles = None
  reg = obs_metrics.active()
  if reg is not None:
    # the loop owns the step accounting the detectors read: train.steps
    # bumps by K per fused dispatch (bursts — obs/anomaly.py discounts
    # one-slab quantization via this gauge), train.slab spans each
    # dispatch. Don't ALSO wrap loop calls in a StepTimer, or steps
    # double-count.
    reg.gauge("train.unroll").set(unroll)
    obs_handles = (reg.counter("train.steps"), obs_spans.active())
  return TrainLoop(step, fused, unroll, obs_handles)


def shard_batch(batch, mesh, extra_axes: Tuple[str, ...] = ()):
  """Place a host batch onto the mesh with batch sharding."""
  import jax
  sharding = batch_sharding(mesh, extra_axes)
  return jax.tree.map(lambda x: jax.device_put(x, sharding), batch)
