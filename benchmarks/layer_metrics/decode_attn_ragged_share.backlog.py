"""model step, serving: percent of the window's per-slot single-token cache
reads (one a layer application a decode step: a layer's attention of each
slot's new token over that slot's K and V leaves, once a pass of a looped
model) that the fused decode dispatches made with ``ops.decode_attention``,
the kernel that brings only the blocks of rows below each slot's cursor from
HBM, and not with the dense contraction over all ``max`` positions, d
``decode_attn_reads_ragged`` / d ``decode_attn_reads`` (the program's
counters: ``SlotDecoder`` knows at trace time which lowering each read of its
``step_many`` program took).  Under 100 some read fell back to the dense
path: a leaf that is not bf16 (an int8 cache and its scales), a minor axis
off whole lanes or a position axis off whole blocks of 256, a head size that
neither divides nor is a multiple of 128, a sliding window, a mesh of more
than one device, Pallas kernels off (the CPU).  A program without the
counters (the parent of PR 31) or without such reads (the paged pool, MLA
and KDA layers) reads nothing."""


def read(report):
  d = report.get("stats_delta") or {}
  if not d.get("decode_attn_reads") or "decode_attn_reads_ragged" not in d:
    return None
  return 100.0 * d["decode_attn_reads_ragged"] / d["decode_attn_reads"]
