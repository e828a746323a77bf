"""HBM held by the paged LATENT cache (`latent_pool_bytes` of `metrics_summary()`, through the program's obs registry): one shared row a position a latent layer, padding and trash block included, no V half; nothing where the pool keeps per-head K and V or the program has no such gauge."""

from benchmark.harness import latent_readers

read = latent_readers.latent_pool_gb
