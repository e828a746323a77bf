"""The latent decode kernel (latent_decode*) against the work latent_work.latent_decode reckons from the traced ticks' live context lengths: the absorbed form, 2 x (576 + 512) x 32 a cached position, the live blocks' rows once (no V term), the absorbed queries and the heads' sums."""

from benchmark.harness import latent_readers

read = latent_readers.latent_decode_roofline
