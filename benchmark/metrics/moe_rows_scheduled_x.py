"""Rows of products the grouped expert products' schedule multiplies over the live (token, expert) pairs, one decode call over every slot plus one chunk call a layer at a balanced router (experts per token x held / published pairs a token, spread evenly over the held experts), from the program's `ops.grouped_matmul.scheduled_rows` at the cell's geometry (the experts from the family's `sizes`, slots and chunk from the deployment); 1 = no padded row, XLA's 512 rows a held expert read 37.6; nothing where the program has no such function, the deployment states no chunk or the family holds no routed expert."""

from typing import Any, Optional


def read(run: Any) -> Optional[float]:
    try:
        from trustworthy_dl_tpu.ops.grouped_matmul import scheduled_rows
    except ImportError:        # a program from before the kernel
        return None
    deployment = run.config.get("deployment") or {}
    serve = deployment.get("serve_config")
    chunk = deployment.get("prefill_chunk_positions")
    if not serve or not chunk:
        return None
    shape = run.family.sizes(run.config)
    held, per_token = shape.get("n_experts_held"), shape.get("experts_per_tok")
    if not held or not per_token:
        return None
    rows = live = 0
    for tokens in (int(serve["max_slots"]), int(chunk)):
        pairs = tokens * per_token * held // shape["n_experts"]
        sizes = [pairs // held + (g < pairs % held) for g in range(held)]
        rows += scheduled_rows(tokens * per_token, sizes)
        live += pairs
    return rows / live if live else None
