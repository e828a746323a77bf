"""The chunk program's latent attention kernel (latent_prefill*) against the LEAST work either form of it needs (latent_work.latent_prefill): 2 x (192 + 128) x 32 a causal pair, the cached blocks' rows once, Q and O; an absorbed kernel cannot read over 29 %, an expanded one pays its expansion uncounted, so the share means the same whichever form the program keeps."""

from benchmark.harness import latent_readers

read = latent_readers.latent_prefill_roofline
