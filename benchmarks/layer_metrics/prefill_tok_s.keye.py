"""model step, serving: prompt tokens prefilled per second of prefill, the
very reading of ``prefill_tok_s.kimi`` (prompt lengths over prefill spans of
the requests the window finished, from the engine's per-request ledger); in
this cell a span is up to 8 chunks of 4096 tokens (each reading the weights
every token passes and the 16 held experts' stacks; a chunk that ends above
2048 positions scores its queries against the row's index keys in blocks of
2048, selects 2048 a query exactly and attends under the keep operand: the
first chunk itself through the flash forward, a later one its row in blocks of
2048), the wait for the last and the insert of eighteen leaves' rows."""

from benchmarks.lib import loader

read = loader.load_module("layer_metrics", "prefill_tok_s.kimi").read
