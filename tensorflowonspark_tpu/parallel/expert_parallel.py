"""Expert parallelism: a mixture-of-experts FFN sharded over the
``expert`` mesh axis.

Beyond-parity capability (SURVEY.md §2.3: "Expert parallelism: No"): each
device of the ``expert`` axis holds a disjoint slice of the expert stack;
tokens are dispatched with one-hot combine weights (Shazeer-style einsum
dispatch) and partial expert outputs are combined with a single ``psum``
over the expert axis. Top-1 or top-k routing (renormalized combine
weights) with a Switch/GShard :func:`load_balancing_loss`; gating runs
replicated (it is a tiny
matmul), expert FFNs run sharded.

Two dispatch strategies:

- :func:`moe_ffn` — dense masked dispatch: every token visits every expert
  shard (masked), combined with one psum. Exact and simple.
- :func:`moe_ffn_a2a` — GShard-style all-to-all token exchange with
  capacity bounds: each device runs only its experts on only their
  assigned tokens (the communication-optimal variant).

And the ONE CHIP'S SHARE of an expert-parallel layer (ROADMAP R2), which
needs no mesh: :func:`route_sigmoid_topk` routes over the router's whole
width, :func:`held_experts_ffn` computes what the experts held HERE add for
the tokens routed to them, dropping none.
"""

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from tensorflowonspark_tpu import ops
from tensorflowonspark_tpu.parallel import mesh as mesh_lib


def init_moe_params(rng, num_experts: int, d_model: int, d_ff: int,
                    dtype=jnp.float32) -> Dict[str, jax.Array]:
  kg, k1, k2 = jax.random.split(rng, 3)
  scale_in = 1.0 / (d_model ** 0.5)
  return {
      "w_gate": jax.random.normal(kg, (d_model, num_experts), dtype) * scale_in,
      "w_up": jax.random.normal(k1, (num_experts, d_model, d_ff), dtype)
              * scale_in,
      "w_down": jax.random.normal(k2, (num_experts, d_ff, d_model), dtype)
                * (1.0 / (d_ff ** 0.5)),
  }


def _router_probs(x, w_gate):
  """Router forward: softmax probabilities [T, E] — the single source of
  the gating math for every dispatch strategy and the aux loss."""
  logits = x.astype(jnp.float32) @ w_gate.astype(jnp.float32)
  return jax.nn.softmax(logits, axis=-1)


def _topk_dispatch(probs, top_k: int):
  """Binary multi-hot dispatch [T, E] selecting each token's top-k experts."""
  _, idx = lax.top_k(probs, top_k)
  return jax.nn.one_hot(idx, probs.shape[-1],
                        dtype=probs.dtype).sum(axis=1)


def _combine_weights(probs, dispatch, top_k: int):
  """Combine weights [T, E] for a multi-hot dispatch: gate probabilities,
  renormalized over the selected set for top_k > 1. The single source of
  this math for every dispatch strategy."""
  selected = probs * dispatch
  if top_k == 1:
    return selected
  return selected / jnp.sum(selected, axis=-1, keepdims=True)


def route(params, x, top_k: int = 1):
  """Top-k routing: (dispatch [T,E] multi-hot, combine [T,E], probs [T,E]).

  Dispatch selects which experts process each token (binary — experts see
  the raw token); combine weights each selected expert's output by its
  gate probability (renormalized over the selected set for top_k > 1).
  Returns the router probabilities too so callers can derive the
  load-balancing loss without a second router forward.
  """
  probs = _router_probs(x, params["w_gate"])
  dispatch = _topk_dispatch(probs, top_k)               # [T, E]
  return dispatch, _combine_weights(probs, dispatch, top_k), probs


def _route(params, x, top_k: int = 1):
  return route(params, x, top_k)[:2]


def load_balancing_loss(params, x, top_k: int = 1):
  """Auxiliary load-balancing loss (Switch/GShard style).

  ``E · Σ_e fraction_of_tokens_routed_to_e · mean_router_prob_e`` — equals
  1.0 under perfectly uniform routing; add a small multiple to the task
  loss to keep experts utilized.
  """
  probs = _router_probs(x, params["w_gate"])
  dispatch = _topk_dispatch(probs, top_k)
  return aux_loss_from(probs, dispatch, top_k)


def aux_loss_from(probs, dispatch, top_k: int = 1):
  """Load-balancing loss from an existing routing (no router recompute)."""
  fraction = jnp.mean(dispatch, axis=0) / top_k         # [E]
  mean_prob = jnp.mean(probs, axis=0)                   # [E]
  return probs.shape[-1] * jnp.sum(fraction * mean_prob)


def moe_ffn_reference(params, x, top_k: int = 1, routing=None):
  """Single-device reference: x [T, D] -> [T, D]. ``routing`` optionally
  supplies a precomputed (dispatch, combine) pair from :func:`route`."""
  dispatch, combine = routing if routing is not None \
      else _route(params, x, top_k)                    # [T, E] each
  xf = x.astype(jnp.float32)
  h = jax.nn.relu(jnp.einsum("te,td,edf->etf", dispatch, xf,
                             params["w_up"].astype(jnp.float32)))
  out = jnp.einsum("etf,efd->etd", h,
                   params["w_down"].astype(jnp.float32))
  return jnp.einsum("etd,te->td", out, combine).astype(x.dtype)


def _moe_local(x, dispatch, combine, w_up, w_down):
  """shard_map body: local expert slice. x [T,D] replicated over expert;
  dispatch/combine [T,E_local]; w_up [E_local,D,F]; w_down [E_local,F,D]."""
  xf = x.astype(jnp.float32)
  h = jax.nn.relu(jnp.einsum("te,td,edf->etf", dispatch, xf,
                             w_up.astype(jnp.float32)))
  out = jnp.einsum("etf,efd->etd", h, w_down.astype(jnp.float32))
  partial = jnp.einsum("etd,te->td", out, combine)
  return lax.psum(partial, mesh_lib.AXIS_EXPERT).astype(x.dtype)


def moe_ffn(params, x, mesh, top_k: int = 1, routing=None):
  """Expert-sharded MoE FFN. x: [tokens, d_model] (shard tokens over the
  data axes as usual); expert weights sharded over the expert axis."""
  from tensorflowonspark_tpu.utils.compat import jax_shard_map as shard_map

  dispatch, combine = routing if routing is not None \
      else _route(params, x, top_k)                    # [T, E] replicated
  batch_axes = mesh_lib.data_axes(mesh) or None
  fn = shard_map(
      _moe_local, mesh=mesh,
      in_specs=(P(batch_axes), P(batch_axes, mesh_lib.AXIS_EXPERT),
                P(batch_axes, mesh_lib.AXIS_EXPERT),
                P(mesh_lib.AXIS_EXPERT), P(mesh_lib.AXIS_EXPERT)),
      out_specs=P(batch_axes), check_vma=False)
  return fn(x, dispatch, combine, params["w_up"], params["w_down"])


def _moe_a2a_local(x, w_gate, w_up, w_down, capacity: int, top_k: int):
  """shard_map body for all-to-all dispatch (GShard-style).

  x: [T_local, D] (tokens sharded over data×expert axes);
  w_gate replicated [D, E]; w_up/w_down sharded [E_local, ...].
  Tokens route to their top-k global experts, dispatch tensors are
  exchanged over the ``expert`` axis with two all-to-alls, and each device
  runs only its own experts on only their assigned tokens
  (capacity-bounded; overflow (token, expert) assignments are dropped, the
  standard GShard capacity semantics).
  """
  xf = x.astype(jnp.float32)
  probs = _router_probs(x, w_gate)                  # [T, E]
  mh = _topk_dispatch(probs, top_k)                 # [T, E] binary multi-hot
  combine_w = _combine_weights(probs, mh, top_k)
  # position of each (token, expert) assignment in that expert's queue
  pos = (jnp.cumsum(mh, axis=0) - 1.0) * mh                      # [T, E]
  keep = mh * (pos < capacity)
  dispatch = keep[:, :, None] * jax.nn.one_hot(
      pos.astype(jnp.int32), capacity, dtype=jnp.float32)        # [T, E, C]
  combine = dispatch * combine_w[:, :, None]                     # [T, E, C]

  expert_in = jnp.einsum("tec,td->ecd", dispatch, xf)   # [E, C, D]
  # exchange: every device sends each peer its slice of the expert dim
  expert_in = lax.all_to_all(expert_in, mesh_lib.AXIS_EXPERT,
                             split_axis=0, concat_axis=1, tiled=True)
  h = jax.nn.relu(jnp.einsum("ecd,edf->ecf", expert_in,
                             w_up.astype(jnp.float32)))
  out = jnp.einsum("ecf,efd->ecd", h, w_down.astype(jnp.float32))
  out = lax.all_to_all(out, mesh_lib.AXIS_EXPERT,
                       split_axis=1, concat_axis=0, tiled=True)
  y = jnp.einsum("ecd,tec->td", out, combine)
  return y.astype(x.dtype)


def moe_ffn_a2a(params, x, mesh, capacity_factor: float = 2.0,
                top_k: int = 1):
  """Expert-parallel MoE with all-to-all token dispatch.

  Communication-optimal variant of :func:`moe_ffn`: tokens are sharded
  over the data AND expert axes, each device dispatches its tokens to the
  owning experts with two ``all_to_all`` collectives (ICI neighbor
  traffic), and only capacity-bounded expert work runs per device —
  instead of every device touching every token. Top-k routing with
  capacity ``ceil(T_local · k / E) * capacity_factor`` per expert per
  shard; overflow assignments contribute zero output (standard GShard
  semantics; with top-k > 1 a token's surviving experts keep their
  renormalized weights).
  """
  from tensorflowonspark_tpu.utils.compat import jax_shard_map as shard_map

  num_experts = params["w_gate"].shape[-1]
  batch_axes = mesh_lib.data_axes(mesh)
  token_axes = tuple(batch_axes) + (mesh_lib.AXIS_EXPERT,)
  shards = mesh_lib.axis_size(mesh, *token_axes)
  t_local = x.shape[0] // shards
  capacity = max(1, int(-(-t_local * top_k // num_experts) * capacity_factor))

  fn = functools.partial(_moe_a2a_local, capacity=capacity, top_k=top_k)
  return shard_map(
      fn, mesh=mesh,
      in_specs=(P(token_axes), P(), P(mesh_lib.AXIS_EXPERT),
                P(mesh_lib.AXIS_EXPERT)),
      out_specs=P(token_axes), check_vma=False)(
          x, params["w_gate"], params["w_up"], params["w_down"])


# ---------------------------------------------------------------------------
# one chip's share: routed over all experts, computed for the experts here
# ---------------------------------------------------------------------------


def kept_groups(choice, groups: int, kept: int):
  """The GROUP limit of a router: ``choice [T, E]`` (the scores the selection
  reads, bias included) lie in ``groups`` groups of ``E // groups``
  consecutive experts; a group's score is the sum of its two largest, and a
  token keeps its ``kept`` best groups. Returns ``[T, groups]`` bool."""
  t, e = choice.shape
  best2, _ = lax.top_k(choice.reshape(t, groups, e // groups), 2)
  _, chosen = lax.top_k(jnp.sum(best2, axis=-1), kept)          # [T, kept]
  return jnp.any(chosen[..., None] == jnp.arange(groups), axis=1)


def route_sigmoid_topk(x, router, bias, top_k: int, scale: float = 1.0,
                       groups: int = 0, groups_kept: int = 0):
  """Sigmoid top-k routing over the router's WHOLE width, in float32 (a
  rounded score moves a near-tie at the k-th place to another expert).

  ``x [T, D]``, ``router [D, E]``, ``bias [E]`` (added for the SELECTION
  only). Returns ``(experts [T, k] int32, weights [T, k] f32)``: the k
  largest of ``s + bias`` with ``s = sigmoid(x W)``, weighted ``s_e / sum
  of the selected s`` times ``scale``. With ``groups`` > 0 the selection is
  limited to each token's ``groups_kept`` best groups (:func:`kept_groups`):
  an expert of another group cannot be chosen, whatever its score, and a
  third member says which groups each token kept (``[T, groups]`` bool)."""
  s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                             router.astype(jnp.float32),
                             precision=lax.Precision.HIGHEST))
  choice = s + bias.astype(jnp.float32)
  if groups:
    kept = kept_groups(choice, groups, groups_kept)
    choice = jnp.where(jnp.repeat(kept, s.shape[-1] // groups, axis=-1),
                       choice, -jnp.inf)
  _, experts = lax.top_k(choice, top_k)
  picked = jnp.take_along_axis(s, experts, axis=-1)
  out = (experts.astype(jnp.int32),
         picked / jnp.sum(picked, axis=-1, keepdims=True) * scale)
  return out + (kept,) if groups else out


def route_softmax_topk(x, router, top_k: int, scale: float = 1.0):
  """Softmax top-k routing over the router's WHOLE width, in float32 under
  ``Precision.HIGHEST`` (as :func:`route_sigmoid_topk`: a rounded score moves
  a near-tie at the k-th place): ``p = softmax(x W)``, the ``top_k`` largest,
  weights ``p_e / sum of the chosen p`` times ``scale``. No bias, no groups.
  ``x [T, D]``, ``router [D, E]``; returns ``(experts [T, k] int32, weights
  [T, k] f32)``."""
  p = jax.nn.softmax(jnp.dot(x.astype(jnp.float32),
                             router.astype(jnp.float32),
                             precision=lax.Precision.HIGHEST), axis=-1)
  picked, experts = lax.top_k(p, top_k)
  return (experts.astype(jnp.int32),
          picked / jnp.sum(picked, axis=-1, keepdims=True) * scale)


def held_experts_ffn(x, experts, weights, gate, up, down, first: int = 0,
                     split=None, mesh=None, tally=None):
  """What the experts held here add to the layer: ``sum over a token's
  assignments to experts in [first, first + held) of w * down_e(silu(gate_e
  x) * up_e x)``; assignments to experts held elsewhere add nothing.

  ``x [T, D]``; ``experts``/``weights [T, k]`` from
  :func:`route_sigmoid_topk` or :func:`route_softmax_topk`; ``gate``/``up [held, D, F]``, ``down
  [held, F, D]`` in the compute dtype. The ``T * k`` assignments are sorted
  by expert (those held elsewhere last, outside every group) and multiplied
  as ONE grouped product a matrix (rows of a group meet that group's expert
  only, f32 accumulation), whatever the imbalance: there is no capacity, so
  no token is dropped, and an expert nobody chose is a group of no rows. One
  product, two lowerings, chosen from what the code can observe:
  ``ops.expert_product``'s kernel, which reads only the rows that have a
  group and streams only the matrices that have rows, for bf16 operands of
  whole lanes on ONE device (GSPMD does not partition a Mosaic call; ``mesh``
  is the caller's), and ``lax.ragged_dot``, which costs each touched group
  about three times its matrix's bytes' time whatever its rows, for everything
  else (a float32 stack: a test, an init). ``tally`` (a dict,
  ``models.transformer.expert_product_tally``) is told of each product and of
  those that took the kernel, while the call traces. ``split`` turns an
  activation into the list of
  arrays of the weights' dtype that SUM to it (default: one cast); with n
  terms each assignment's row goes in n times, term after term, into a
  group n times as long, and the n results are added: float32 activations
  times bf16 weights at the price of n x the rows, not n x the weight
  bytes. Returns ``(y [T, D] f32, held [T, k] bool)``."""
  t, k = experts.shape
  n_held = gate.shape[0]
  local = experts - first
  held = jnp.logical_and(local >= 0, local < n_held)
  key = jnp.where(held, local, n_held).reshape(-1)          # [T * k]
  order = jnp.argsort(key, stable=True)
  sizes = jnp.bincount(key, length=n_held + 1)[:n_held].astype(jnp.int32)
  rows = jnp.take(x, order // k, axis=0)                    # [T * k, D]

  def grouped(lhs, rhs):
    parts = split(lhs) if split is not None else [lhs.astype(rhs.dtype)]
    n = len(parts)
    lhs = jnp.stack(parts, axis=1).reshape(-1, lhs.shape[-1])
    kernel = ((mesh is None or mesh.size == 1)
              and ops.expert_product_supports(lhs.shape, lhs.dtype,
                                              rhs.shape, rhs.dtype))
    if tally is not None:
      tally["products"] += 1
      tally["kernel"] += kernel
    if kernel:
      out = ops.expert_product(lhs, rhs, sizes * n,
                               interpret=ops.pallas_interpret())
    else:
      out = lax.ragged_dot(
          lhs, rhs, sizes * n, preferred_element_type=jnp.float32,
          # float32 operands (a test, an init): not one rounded bf16 pass
          precision=lax.Precision.HIGHEST if lhs.dtype == jnp.float32
          else None)
    return out.reshape(-1, n, out.shape[-1]).sum(axis=1)

  hidden = jax.nn.silu(grouped(rows, gate)) * grouped(rows, up)
  out = grouped(hidden, down)                               # [T * k, D]
  w = jnp.where(held, weights, 0.0).reshape(-1)[order]
  # rows past the last group belong to no expert here: the kernel leaves
  # zeros there, ragged_dot whatever it left, which is not a number to scale
  out = jnp.where((w > 0)[:, None], out * w[:, None], 0.0)
  # back to assignment order: a gather by the inverse permutation
  y = jnp.take(out, jnp.argsort(order), axis=0).reshape(t, k, -1).sum(axis=1)
  return y, held


def shard_moe_params(params, mesh):
  """Place MoE params: gate replicated, expert stacks sharded."""
  from jax.sharding import NamedSharding
  put = lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec))  # noqa: E731
  return {
      "w_gate": put(params["w_gate"], P()),
      "w_up": put(params["w_up"], P(mesh_lib.AXIS_EXPERT)),
      "w_down": put(params["w_down"], P(mesh_lib.AXIS_EXPERT)),
  }
