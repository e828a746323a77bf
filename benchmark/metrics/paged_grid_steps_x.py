"""Grid steps a layer of the two paged attention kernels, one chunk call plus one decode call over every slot, over the fewest any schedule has (one step a block-table row and logical block: rows x nbps of each call), from the program's `grid_steps` at the cell's geometry; 1 = a step holds every head of a block and the whole chunk; nothing where the program has no such function."""

import math
from typing import Any, Optional


def read(run: Any) -> Optional[float]:
    try:
        from trustworthy_dl_tpu.models.gpt2 import GPT2Config
        from trustworthy_dl_tpu.ops.paged_attention import grid_steps
    except ImportError:        # a program from before the counter
        return None
    deployment = run.config.get("deployment") or {}
    serve = deployment.get("serve_config")
    chunk = deployment.get("prefill_chunk_positions")
    if not serve or not serve.get("paged") or not chunk:
        return None
    heads = int(run.config["n_head"])
    width = int(run.config["n_embd"]) // heads
    block = int(serve["block_size"])
    nbps = int(serve["max_seq"]) // block
    kv_dtype = serve["kv_dtype"]
    if kv_dtype == "model":
        kv_dtype = GPT2Config.dtype
    calls = (("prefill", 1, int(chunk)), ("decode", int(serve["max_slots"]), 1))
    steps = sum(math.prod(grid_steps(program, rows, heads, nbps, t, width,
                                     block, kv_dtype))
                for program, rows, t in calls)
    return steps / sum(rows * nbps for _, rows, _ in calls)
