"""model step, serving: prompt tokens prefilled per second of prefill, the
very reading of ``prefill_tok_s.kimi`` (prompt lengths over prefill spans of
the requests the window finished, from the engine's per-request ledger); in
this cell a span is up to 6 chunks of 2048 tokens (each reading 3.26 GB of
weights every token passes and the experts' stacks, each attending through
the flash forward at 128 heads with keys of 192 / values of 128 expanded from
the latent: the first itself, the later ones their row in blocks of 2048
expanded as they are met), the wait for the last and the insert of five
latent rows."""

from benchmarks.lib import loader

read = loader.load_module("layer_metrics", "prefill_tok_s.kimi").read
