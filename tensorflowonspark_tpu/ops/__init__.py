"""Pallas TPU kernels for hot ops.

The reference had no custom kernels (all math delegated to TF); on TPU the
few ops XLA cannot fuse optimally are written in Pallas:

- ``flash_attention`` — fused blockwise attention (softmax never
  materializes the full score matrix in HBM); the intra-block engine under
  ring attention's sequence parallelism.
"""

#: env registry (tools.analyze TOS008): "0"/"1" force real/interpret
#: Pallas execution; unset/"auto" = interpret off-TPU, real kernels on TPU
ENV_PALLAS_INTERPRET = "TOS_PALLAS_INTERPRET"


def pallas_interpret() -> bool:
  """Whether Pallas kernels should run in interpret (emulation) mode.

  Default policy: interpret off-TPU (how CPU CI trains through the
  production kernel paths), real Mosaic lowering on TPU. Override with
  ``TOS_PALLAS_INTERPRET=0`` to force real kernels even when the default
  backend is not TPU — that is how the deviceless Mosaic gate
  (tools/mosaic_gate.py) AOT-compiles every production kernel against a
  TPU topology from a CPU-only host, with no chip claimed. ``=1`` forces
  interpret everywhere (debugging on-chip numerics).
  """
  import os
  v = os.environ.get(ENV_PALLAS_INTERPRET, "auto").lower()
  if v in ("0", "false"):
    return False
  if v in ("1", "true"):
    return True
  import jax
  return jax.default_backend() != "tpu"


def pallas_kernels_enabled() -> bool:
  """Whether "auto" impl settings should pick the Pallas kernels at all.

  Distinct from :func:`pallas_interpret` (HOW kernels run) — this decides
  WHETHER "auto" uses them: on the real TPU backend, or under
  ``TOS_PALLAS_INTERPRET=0`` (the deviceless gate compiling FOR a TPU
  topology from a CPU client). ``TOS_PALLAS_INTERPRET=1`` on a TPU does
  NOT disable them — the kernels stay selected and run in interpret mode,
  which is the flag's on-chip numerics-debugging purpose.
  """
  import os
  if os.environ.get(ENV_PALLAS_INTERPRET, "").lower() in ("0", "false"):
    return True
  import jax
  return jax.default_backend() == "tpu"


from tensorflowonspark_tpu.ops.flash_attention import (  # noqa: F401,E402
    flash_attention, flash_attention_block, flash_attention_sharded,
    merge_partials,
)
from tensorflowonspark_tpu.ops.layer_norm import (  # noqa: F401
    layer_norm, layer_norm_sharded,
)
from tensorflowonspark_tpu.ops.cursor_write import (  # noqa: F401
    cursor_write, supports as cursor_write_supports,
)
from tensorflowonspark_tpu.ops.decode_attention import (  # noqa: F401
    decode_attention, supports as decode_attention_supports,
)
from tensorflowonspark_tpu.ops.expert_product import (  # noqa: F401
    expert_product, supports as expert_product_supports,
)
from tensorflowonspark_tpu.ops.select_topk import (  # noqa: F401
    select_topk, supports as select_topk_supports,
)
