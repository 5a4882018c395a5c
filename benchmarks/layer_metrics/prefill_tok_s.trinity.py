"""model step, serving: prompt tokens prefilled per second of prefill, the
very reading of ``prefill_tok_s.kimi`` (prompt lengths over prefill spans of
the requests the window finished, from the engine's per-request ledger); in
this cell a span is up to 24 chunks of 512 tokens (each reading all 8.6 GB of
weights, the later ones attending their row cache in blocks through the
flash kernel), the wait for the last and the insert of a whole-context row
and four rings."""

from benchmarks.lib import loader

read = loader.load_module("layer_metrics", "prefill_tok_s.kimi").read
