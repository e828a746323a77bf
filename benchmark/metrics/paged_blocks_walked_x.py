"""Blocks the two paged attention kernels copy over the blocks that hold live positions, over the traced ticks (`run.counters["trace_ticks"]`, the lengths and chunks the rooflines take): the program's `ops.paged_attention.walked_blocks` for every row of a tick's decode call (its live lengths, and a row that decodes nothing for every other slot) and for each of its chunks, at the cell's geometry (heads and width from the family's `attention_layers`, the pool's type from its `compute_dtype` where `kv_dtype` is `model`), over ceil(length / block) a decode row and ceil((pos + rows) / block) a chunk; 1 = a walk ends with its row's last live block, 64 blocks a row whatever it held read 1.79 on the recorded tick; nothing on a program without the function, without traced ticks, where a tick held other work than told, where the deployment states no chunk or the family has no paged attention layer."""

import math
from typing import Any, Optional


def read(run: Any) -> Optional[float]:
    try:
        from trustworthy_dl_tpu.ops.paged_attention import walked_blocks
    except ImportError:        # a program from before the counter
        return None
    ticks = run.counters.get("trace_ticks")
    deployment = run.config.get("deployment") or {}
    serve = deployment.get("serve_config")
    chunk = deployment.get("prefill_chunk_positions")
    if not ticks or not serve or not chunk:
        return None
    if any(t["tokens"] != t["expected"] for t in ticks):
        return None                 # the ticks held other work than told
    block = int(serve["block_size"])
    nbps = int(serve["max_seq"]) // block
    slots = int(serve["max_slots"])
    kv_dtype = serve["kv_dtype"]
    if kv_dtype == "model":
        kv_dtype = run.family.compute_dtype(run.config)
    walked = live = 0
    for layers, heads, kv_heads, width in run.family.attention_layers(
            run.config):
        shape = dict(head_dim=width, block_size=block, kv_dtype=kv_dtype,
                     kv_heads=kv_heads)
        for tick in ticks:
            rows = list(tick["decode"])
            if rows:            # the decode call walks every slot's row
                rows += [0] * (slots - len(rows))
            walked += layers * (
                sum(walked_blocks("decode", n, heads, nbps, 1, **shape)
                    for n in rows)
                + sum(walked_blocks("prefill", (pos, r), heads, nbps,
                                    int(chunk), **shape)
                      for pos, r in tick["prefill"]))
            live += layers * (
                sum(math.ceil(n / block) for n in tick["decode"])
                + sum(math.ceil((pos + r) / block)
                      for pos, r in tick["prefill"]))
    return walked / live if live else None
