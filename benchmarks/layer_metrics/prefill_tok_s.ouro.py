"""model step, serving: prompt tokens prefilled per second of prefill, the
very reading of ``prefill_tok_s.kimi`` (prompt lengths over prefill spans of
the requests the window finished, from the engine's per-request ledger); in
this cell a span is one padded chunk through 4 passes of 48 layers, the wait
for it and the insert of 384 row leaves."""

from benchmarks.lib import loader

read = loader.load_module("layer_metrics", "prefill_tok_s.kimi").read
