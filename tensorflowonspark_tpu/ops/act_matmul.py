"""Fused GELU + matmul as a Pallas TPU kernel: ``gelu(x) @ W``.

The MLP down-projection twin of :mod:`ops.ln_matmul` (round-2 verdict
item 7's MFU hunt; the round-3 verdict named the "MLP down-proj pair" a
candidate for the next fusion): in every Transformer MLP the down-proj
matmul consumes a GELU output, and XLA materializes that activation in
HBM between the two HLOs. At d_ff = 4·d_model the [rows, d_ff] GELU
activation is the WIDEST tensor in the block — four times the LN
round-trip ln_matmul eliminates — so this kernel computes GELU on the
VPU and feeds the activated block straight into the MXU dot from VMEM.

Forward layout: x [..., F] (pre-activation, leading dims flatten to
rows), W [F, N]. Grid tiles (rows, N); each (i, j) step re-applies GELU
to its x block — one extra VPU pass per N-tile, cheaper than an HBM
round-trip of the [rows, F] activated tensor.

Backward: a custom VJP recomputes GELU and its derivative in plain XLA
(the backward is matmul-bound; the fusion win is the forward). GELU is
the tanh approximation, matching ``flax.linen.gelu``'s default so the
fused and unfused model paths are numerically interchangeable.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tensorflowonspark_tpu.ops.layer_norm import _pick_block
from tensorflowonspark_tpu.ops.ln_matmul import _pick_col_block


def _gelu_f32(x):
  # tanh-approximate GELU in f32 (flax nn.gelu default approximate=True)
  return jax.nn.gelu(x, approximate=True)


def effective_blocks(rows: int, f: int, n: int, blk_rows: int,
                     blk_cols: int, w_itemsize: int = 2):
  """The (row, col) block pair the kernel will ACTUALLY run.

  Here the CONTRACTED dim F = d_ff is the LARGE one (unlike ln_matmul,
  which contracts d_model), so both tiles carry byte-footprint caps or
  big-F f32 shapes blow VMEM at the default block sizes (the failure
  mode layer_norm._pick_block records): the x block keeps a f32
  activation copy (itemsize=4 cap) and the [F, blk_n] W tile is held to
  ~4 MiB with a 128-lane floor. Shared with tools/tpu_validate's block
  sweep so its dedup/labels track these caps exactly.
  """
  blk_r = _pick_block(rows, blk_rows, f, itemsize=4)
  cap = max(128, (4 << 20) // (f * w_itemsize))
  return blk_r, _pick_col_block(n, min(blk_cols, cap))


def _act_matmul_kernel(x_ref, w_ref, o_ref):
  x = x_ref[...].astype(jnp.float32)                 # [blk_r, F]
  a = _gelu_f32(x)
  w = w_ref[...]                                     # [F, blk_n]
  acc = jax.lax.dot_general(
      a.astype(w.dtype), w, (((1,), (0,)), ((), ())),
      preferred_element_type=jnp.float32)
  o_ref[...] = acc.astype(o_ref.dtype)


# jitted under the name a device trace should show (ops/layer_norm.py)
@functools.partial(jax.jit, static_argnames=("blk_rows", "blk_cols",
                                             "interpret"))
def act_matmul_fwd(x, W, blk_rows, blk_cols, interpret):
  shape = x.shape
  f = shape[-1]
  n = W.shape[-1]
  rows = 1
  for s in shape[:-1]:
    rows *= s
  xf = x.reshape(rows, f)
  blk_r, blk_n = effective_blocks(rows, f, n, blk_rows, blk_cols,
                                  W.dtype.itemsize)

  out = pl.pallas_call(
      _act_matmul_kernel,
      grid=(rows // blk_r, n // blk_n),
      in_specs=[
          pl.BlockSpec((blk_r, f), lambda i, j: (i, 0)),
          pl.BlockSpec((f, blk_n), lambda i, j: (0, j)),
      ],
      out_specs=pl.BlockSpec((blk_r, blk_n), lambda i, j: (i, j)),
      out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
      interpret=interpret,
      name="act_matmul_fwd",
  )(xf, W)
  return out.reshape(shape[:-1] + (n,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _act_matmul_vjp(x, W, blk_rows, blk_cols, interpret):
  return act_matmul_fwd(x, W, blk_rows, blk_cols, interpret)


def _fwd_rule(x, W, blk_rows, blk_cols, interpret):
  return act_matmul_fwd(x, W, blk_rows, blk_cols, interpret), (x, W)


def _bwd_rule(blk_rows, blk_cols, interpret, res, g):
  x, W = res
  shape = x.shape
  f = shape[-1]
  xf = x.reshape(-1, f).astype(jnp.float32)
  gf = g.reshape(-1, W.shape[-1])
  # recompute the activation and its derivative via jax AD (keeps the
  # derivative exactly consistent with the forward's tanh approximation)
  a, gelu_vjp = jax.vjp(_gelu_f32, xf)
  a = a.astype(x.dtype)
  # dW = gelu(x)^T @ g ; dx = (g @ W^T) ⊙ gelu'(x)
  dW = jax.lax.dot_general(a, gf.astype(x.dtype), (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)
  ga = jax.lax.dot_general(gf.astype(x.dtype), W, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)
  dx, = gelu_vjp(ga)
  return (dx.reshape(shape).astype(x.dtype), dW.astype(W.dtype))


_act_matmul_vjp.defvjp(_fwd_rule, _bwd_rule)


def gelu_matmul(x, W, blk_rows: int = 128, blk_cols: int = 512,
                interpret: bool = False):
  """``gelu(x) @ W`` with the activated tensor never leaving VMEM.
  x: [..., F] pre-activation; W: [F, N] → [..., N]. Differentiable
  (custom VJP; backward recomputes the activation in XLA)."""
  return _act_matmul_vjp(x, W, blk_rows, blk_cols, interpret)


def gelu_matmul_sharded(x, W, mesh, blk_rows: int = 128,
                        blk_cols: int = 512, interpret: bool = False,
                        batch_axes=None):
  """Fused GELU+matmul applied per-shard through shard_map.

  Unlike :func:`ops.ln_matmul_sharded`, here the CONTRACTED dim (d_ff)
  is the tensor-sharded one in Megatron-style TP: the up-projection
  leaves [rows, F/t] per device, GELU is elementwise-local, and the
  down-projection contracts the local F/t slice — the partial products
  are then summed over the tensor axis (one psum, the same collective
  the unfused down-proj needs, so the fusion adds no communication).

  x: [batch, seq, F] with batch over data(+fsdp), seq over sequence, F
  over tensor (replicated if indivisible); W: [F, N] sharded on F the
  same way; output [batch, seq, N] with N unsharded.
  """
  from tensorflowonspark_tpu.utils.compat import jax_shard_map as shard_map
  from jax import lax
  from jax.sharding import PartitionSpec as P
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib

  if batch_axes is None:
    batch_axes = mesh_lib.data_axes(mesh)
  seq_axis = mesh_lib.AXIS_SEQUENCE \
      if mesh_lib.AXIS_SEQUENCE in mesh.axis_names else None
  tensor_axis = mesh_lib.AXIS_TENSOR \
      if mesh_lib.AXIS_TENSOR in mesh.axis_names else None
  if tensor_axis and (x.shape[-1] % mesh.shape[tensor_axis] != 0
                      or mesh.shape[tensor_axis] == 1):
    tensor_axis = None

  def _body(xs, ws):
    part = _act_matmul_vjp(xs, ws, blk_rows, blk_cols, interpret)
    if tensor_axis:
      part = lax.psum(part, tensor_axis)
    return part

  fn = shard_map(
      _body, mesh=mesh,
      in_specs=(P(batch_axes or None, seq_axis, tensor_axis),
                P(tensor_axis, None)),
      out_specs=P(batch_axes or None, seq_axis, None),
      check_vma=False)
  return fn(x, W)
