"""Query-key pairs the flash kernels' schedule scores a (batch, head), over the pairs causal attention needs (`scheduled_pairs` of the program over `kernel_work.causal_pairs`, over the attention layers of the configuration's family that take the flash kernel; 1 = none wasted; nothing where the program has no such counter or takes the flash kernel in no layer at this shape)."""

from typing import Any, Optional

from benchmark.harness import kernel_work as kw


def read(run: Any) -> Optional[float]:
    try:
        from trustworthy_dl_tpu.ops.flash_attention import scheduled_pairs
    except ImportError:        # a program from before the counter
        return None
    t = run.mix.get("seq_len")
    if t is None:
        return None
    t = int(t)
    scored = needed = 0
    for layers, heads, _, d in run.family.attention_layers(run.config):
        if run.family.takes_flash(run.config, t, d):
            scored += layers * heads * scheduled_pairs(t, d, True)
            needed += layers * heads
    return scored / (needed * kw.causal_pairs(t, t)) if needed else None
