"""Fused LayerNorm + matmul as a Pallas TPU kernel: ``LN(x) @ W``.

The MFU lever this targets (ROADMAP; round-2 verdict item 7): in a
Transformer block every matmul that consumes a LayerNorm output —
ln1 → QKV projection, ln2 → MLP up-projection — makes XLA materialize the
normalized [rows, H] activation in HBM between two HLOs (LN's reductions
block full fusion into the dot). This kernel computes the row statistics
on the VPU and feeds the normalized block STRAIGHT into the MXU dot from
VMEM: the normalized activation never exists in HBM.

Forward layout: x [..., H] (leading dims flatten to rows), w_ln [H],
W [H, N]. Grid tiles (rows, N); each (i, j) step re-derives the row
stats of its x block — one extra VPU reduction per N-tile, cheaper than
an HBM round-trip of the [rows, H] normalized tensor.

Backward: a custom VJP recomputes ``xhat`` in plain XLA (two matmuls +
the standard two-reduction LN backward) — the backward is matmul-bound
and XLA already schedules those well; the fusion win is the forward.
float32 statistics over bfloat16 activations, matching ops.layer_norm.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tensorflowonspark_tpu.ops.layer_norm import _pick_block, _stats


def _ln_matmul_kernel(x_ref, wln_ref, w_ref, o_ref, *, eps: float):
  x = x_ref[...].astype(jnp.float32)                 # [blk_r, H]
  mu, rstd = _stats(x, eps)
  xn = (x - mu) * rstd * wln_ref[...].astype(jnp.float32)
  w = w_ref[...]                                     # [H, blk_n]
  acc = jax.lax.dot_general(
      xn.astype(w.dtype), w, (((1,), (0,)), ((), ())),
      preferred_element_type=jnp.float32)
  o_ref[...] = acc.astype(o_ref.dtype)


def _pick_col_block(n: int, blk_cols: int) -> int:
  """Largest LANE-ALIGNED divisor of ``n`` <= blk_cols, or ``n`` itself
  when none exists. Mosaic accepts a last-dim block only if it is a
  multiple of 128 or the whole dimension — a bare largest-divisor snap
  (1280 cols @ blk 512 → 320) fails real TPU lowering; caught by the
  deviceless gate on the GQA fused-QKV sweep config (its h+2·hk=20-head
  projection has N=1280)."""
  blk = min(blk_cols, n)
  for b in range(blk - blk % 128, 0, -128):
    if n % b == 0:
      return b
  # requested block under the 128-lane floor (or no aligned divisor
  # beneath it): snap UP to the smallest aligned divisor before falling
  # back to one whole-dimension block
  for b in range(128, n, 128):
    if n % b == 0:
      return b
  return n


def effective_blocks(rows: int, h: int, n: int, blk_rows: int,
                     blk_cols: int):
  """The (row, col) block pair the kernel will ACTUALLY run after
  divisor fitting — the forward uses this, and tools/tpu_validate's
  block sweep dedups/labels through it so tuning artifacts can never
  name a configuration the kernel would silently snap away from."""
  return _pick_block(rows, blk_rows, h), _pick_col_block(n, blk_cols)


# jitted under the name a device trace should show (ops/layer_norm.py)
@functools.partial(jax.jit, static_argnames=("eps", "blk_rows", "blk_cols",
                                             "interpret"))
def ln_matmul_fwd(x, w_ln, W, eps, blk_rows, blk_cols, interpret):
  shape = x.shape
  h = shape[-1]
  n = W.shape[-1]
  rows = 1
  for s in shape[:-1]:
    rows *= s
  xf = x.reshape(rows, h)
  wln2 = w_ln.reshape(1, h)
  blk_r, blk_n = effective_blocks(rows, h, n, blk_rows, blk_cols)

  out = pl.pallas_call(
      functools.partial(_ln_matmul_kernel, eps=eps),
      grid=(rows // blk_r, n // blk_n),
      in_specs=[
          pl.BlockSpec((blk_r, h), lambda i, j: (i, 0)),
          pl.BlockSpec((1, h), lambda i, j: (0, 0)),
          pl.BlockSpec((h, blk_n), lambda i, j: (0, j)),
      ],
      out_specs=pl.BlockSpec((blk_r, blk_n), lambda i, j: (i, j)),
      out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
      interpret=interpret,
      name="ln_matmul_fwd",
  )(xf, wln2, W)
  return out.reshape(shape[:-1] + (n,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ln_matmul_vjp(x, w_ln, W, eps, blk_rows, blk_cols, interpret):
  return ln_matmul_fwd(x, w_ln, W, eps, blk_rows, blk_cols, interpret)


def _fwd_rule(x, w_ln, W, eps, blk_rows, blk_cols, interpret):
  return (ln_matmul_fwd(x, w_ln, W, eps, blk_rows, blk_cols, interpret),
          (x, w_ln, W))


def _bwd_rule(eps, blk_rows, blk_cols, interpret, res, g):
  x, w_ln, W = res
  shape = x.shape
  h = shape[-1]
  xf = x.reshape(-1, h).astype(jnp.float32)
  gf = g.reshape(-1, W.shape[-1])
  mu, rstd = _stats(xf, eps)
  xhat = (xf - mu) * rstd                            # [R, H] f32
  y = (xhat * w_ln.astype(jnp.float32)).astype(x.dtype)
  # dW = LN(x)^T @ g ; gy = g @ W^T flows into the LN backward
  dW = jax.lax.dot_general(y, gf.astype(x.dtype), (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)
  gy = jax.lax.dot_general(gf.astype(x.dtype), W, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)
  dw_ln = jnp.sum(gy * xhat, axis=0)
  dy = gy * w_ln.astype(jnp.float32)
  m1 = jnp.mean(dy, axis=-1, keepdims=True)
  m2 = jnp.mean(dy * xhat, axis=-1, keepdims=True)
  dx = rstd * (dy - m1 - xhat * m2)
  return (dx.reshape(shape).astype(x.dtype), dw_ln.astype(w_ln.dtype),
          dW.astype(W.dtype))


_ln_matmul_vjp.defvjp(_fwd_rule, _bwd_rule)


def ln_matmul(x, w_ln, W, eps: float = 1e-6, blk_rows: int = 128,
              blk_cols: int = 512, interpret: bool = False):
  """``layer_norm(x, w_ln) @ W`` with the normalized activation never
  leaving VMEM. x: [..., H]; w_ln: [H]; W: [H, N] → [..., N].
  Differentiable (custom VJP; backward recomputes the norm in XLA).
  """
  return _ln_matmul_vjp(x, w_ln, W, eps, blk_rows, blk_cols, interpret)


def ln_matmul_sharded(x, w_ln, W, mesh, eps: float = 1e-6,
                      blk_rows: int = 128, blk_cols: int = 512,
                      interpret: bool = False, batch_axes=None):
  """Fused LN+matmul applied per-shard through shard_map.

  The sharded-model analog of :func:`ln_matmul`, following the
  ``ops.layer_norm_sharded`` precedent: an unpartitioned ``pallas_call``
  over GSPMD-sharded activations would force XLA to gather them, so the
  kernel maps over shards instead (round-3 verdict item 4 — without this
  the flagship multi-chip training path got no LN→matmul fusion).

  x: [batch, seq, H] with batch sharded over data(+fsdp) and seq
  optionally over the sequence axis; w_ln: [H] replicated; W: [H, N]
  with N split over the tensor axis when divisible (the QKV-heads /
  MLP-up layouts), replicated otherwise. H must be unsharded — the norm
  reduces over it and each device's dot contracts it fully, so the
  forward needs no collectives at all. Gradients: shard_map's transpose
  psums dW / dw_ln over the row (data/sequence) axes, matching the
  dense AD (asserted in tests/test_ops.py).
  """
  from tensorflowonspark_tpu.utils.compat import jax_shard_map as shard_map
  from jax.sharding import PartitionSpec as P
  from tensorflowonspark_tpu.parallel import mesh as mesh_lib

  if batch_axes is None:
    batch_axes = mesh_lib.data_axes(mesh)
  seq_axis = mesh_lib.AXIS_SEQUENCE \
      if mesh_lib.AXIS_SEQUENCE in mesh.axis_names else None
  tensor_axis = mesh_lib.AXIS_TENSOR \
      if mesh_lib.AXIS_TENSOR in mesh.axis_names else None
  if tensor_axis and W.shape[-1] % mesh.shape[tensor_axis] != 0:
    tensor_axis = None   # indivisible column count: keep W replicated
  xspec = P(batch_axes or None, seq_axis, None)
  fn = shard_map(
      lambda xs, wl, ws: _ln_matmul_vjp(xs, wl, ws, eps, blk_rows,
                                        blk_cols, interpret),
      mesh=mesh, in_specs=(xspec, P(None), P(None, tensor_axis)),
      out_specs=P(batch_axes or None, seq_axis, tensor_axis),
      check_vma=False)
  return fn(x, w_ln, W)
