"""Query-key pairs the flash kernels' schedule scores a (batch, head), over the pairs causal attention needs (`scheduled_pairs` of the program over `kernel_work.causal_pairs`; 1 = none wasted; nothing where the program has no such counter or does not take the flash kernel at this shape)."""

from typing import Any, Optional

from benchmark.harness import kernel_work as kw


def read(run: Any) -> Optional[float]:
    try:
        from trustworthy_dl_tpu.models.gpt2 import auto_picks_flash
        from trustworthy_dl_tpu.ops.flash_attention import scheduled_pairs
    except ImportError:        # a program from before the counter
        return None
    t = run.mix.get("seq_len")
    if t is None:
        return None
    t = int(t)
    d = int(run.config["n_embd"]) // int(run.config["n_head"])
    if not auto_picks_flash(t, d):
        return None
    return scheduled_pairs(t, d, True) / kw.causal_pairs(t, t)
