"""Grid steps a layer of the two paged attention kernels, one chunk call plus one decode call over every slot, over the fewest any schedule has (one step a block-table row and logical block: rows x nbps of each call), from the program's `grid_steps` at the cell's geometry, the heads, their width and the pool's type from the configuration's family; 1 = a step holds every head of a block and the whole chunk; nothing where the program has no such function, the deployment states no chunk or the family has no paged attention layer."""

import math
from typing import Any, Optional


def read(run: Any) -> Optional[float]:
    try:
        from trustworthy_dl_tpu.ops.paged_attention import grid_steps
    except ImportError:        # a program from before the counter
        return None
    deployment = run.config.get("deployment") or {}
    serve = deployment.get("serve_config")
    chunk = deployment.get("prefill_chunk_positions")
    if not serve or not chunk:
        return None
    block = int(serve["block_size"])
    nbps = int(serve["max_seq"]) // block
    kv_dtype = serve["kv_dtype"]
    if kv_dtype == "model":
        kv_dtype = run.family.compute_dtype(run.config)
    calls = (("prefill", 1, int(chunk)), ("decode", int(serve["max_slots"]), 1))
    steps = fewest = 0
    for layers, heads, _, width in run.family.attention_layers(run.config):
        steps += layers * sum(
            math.prod(grid_steps(program, rows, heads, nbps, t, width, block,
                                 kv_dtype)) for program, rows, t in calls)
        fewest += layers * sum(rows * nbps for _, rows, _ in calls)
    return steps / fewest if fewest else None
