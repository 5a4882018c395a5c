"""model step, serving: percent of the device's busy seconds that the EXACT
SELECTION's own operations took in the traced stretch of the
``keye-vl2-serve-backlog`` cell: the sum of ``trace_summary.op_group_seconds``
over the names below, over ``busy_s``.

The selection (``models.transformer.select_topk``) is XLA's, not a kernel, so
its operations carry no name of their own: the compiler names a fusion after
the operations it holds, and these are the groups a traced run of the cell
(my chip run, PR 44) shows for it and no other cell's breakdown lists: the
searches' passes, a compare of half a key turned into a count
(``%convert_reduce_fusion``: two thirds of the sum), the float's bits folded
into a key and split into halves (``%bitcast_reduce_fusion``,
``%shift-right-logical_convert_fusion``, ``%select_convert_fusion``), the
candidates' mask and the threshold's bits (``%iota_reduce_fusion``,
``%compare_select_fusion``, ``%add_compare_fusion``) and the keep mask itself
(``%and_or_fusion``).  An attribution BY NAME: a fusion of another layer that
happens to hold the same operations would be counted, and a piece of the
selection fused under another name is not (the index scores' products and the
padding of a narrower width's mask are ``%fusion`` and ``%pad`` among
others: left out), so read it as the size of the thing and not to the percent;
a later compiler may name them otherwise, and the reader then finds less.  A
trace without those groups, or a window without a decode step under a keep
mask (a program without a selection: the parent of PR 44), reads nothing."""

GROUPS = ("%convert_reduce_fusion", "%bitcast_reduce_fusion",
          "%shift-right-logical_convert_fusion", "%select_convert_fusion",
          "%iota_reduce_fusion", "%compare_select_fusion",
          "%add_compare_fusion", "%and_or_fusion")


def read(report):
  summary = report.get("trace_summary") or {}
  groups = summary.get("op_group_seconds") or {}
  d = report.get("stats_delta") or {}
  if not summary.get("busy_s") or not d.get("steps") \
      or not d.get("decode_attn_reads_sparse"):
    return None
  found = [groups[g] for g in GROUPS if g in groups]
  if not found:
    return None
  return 100.0 * sum(found) / summary["busy_s"]
