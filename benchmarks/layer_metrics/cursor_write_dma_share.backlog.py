"""model step, serving: percent of the window's per-slot cursor writes of
cache leaves (one a leaf a decode step: K and V of every layer, an MLA
latent cache) that the fused decode dispatches made with the DMA kernel
``ops.cursor_write`` and not with XLA's loop of bounds-checked update-slices,
d ``cursor_leaf_writes_dma`` / d ``cursor_leaf_writes`` (the program's
counters: ``SlotDecoder`` knows at trace time which lowering each leaf of
its ``step_many`` program took).  Under 100 some leaf fell back to the loop
(a shape off the tiling, a mesh, Pallas kernels off).  A program without the
counters (the parent of PR 29) reads nothing."""


def read(report):
  d = report.get("stats_delta") or {}
  if not d.get("cursor_leaf_writes") or "cursor_leaf_writes_dma" not in d:
    return None
  return 100.0 * d["cursor_leaf_writes_dma"] / d["cursor_leaf_writes"]
