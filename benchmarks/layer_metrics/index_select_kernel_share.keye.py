"""model step, serving: percent of the window's exact selections (the
indexer's top ``sparse_topk`` of a query's candidates, one a layer application
of a prefill chunk and of each step of a fused decode dispatch) whose threshold
search ran in ``ops.select_topk``'s kernel, which reads a tile of rows' scores
once and makes its 32 passes in VMEM over the blocks up to the tile's last
candidate, and not as XLA operations, each pass a read of the whole row from
HBM: d ``index_selections_kernel`` / d ``index_selections`` (the program's
counters: ``models.transformer.select_topk`` notes which lowering it took
while a program traces, ``SlotDecoder.index_selections`` keeps a program's
pair, ``ServingEngine`` adds it a dispatch).  Under 100 some selection fell
back: a row that is not whole lanes (the rehearsal's toy widths), scores that
are not float32, a mesh of more than one device.  A program without the
counters (the parent of PR 45) or without a selection (no
``sparse_topk``) reads nothing."""


def read(report):
  d = report.get("stats_delta") or {}
  if not d.get("index_selections") or "index_selections_kernel" not in d:
    return None
  return 100.0 * d["index_selections_kernel"] / d["index_selections"]
