"""What the expert-layer and recurrent-state readers share: the counters of
the program's obs registry, which ``metrics_summary()`` fills (the engine
pulls them off the device only there).  The driver asks for a summary when
the window opens and when it closes, so ``scope="since_last_summary"`` holds
the WINDOW's counts.  A program without these counters (GPT-2's engine, a
program from before they existed, a run that built no engine) reads None."""

from __future__ import annotations

from typing import Any, List, Optional

PAIRS = "tddl_serve_moe_held_expert_pairs"
TOKENS = "tddl_serve_moe_tokens_fed"
STATE_BYTES = "tddl_serve_state_pool_bytes"
WINDOW = "since_last_summary"


def _series(name: str) -> List[Any]:
    """Every series of the program's metric ``name`` as the registry's
    snapshot gives it (``labels`` and ``value``), none where there is no
    such metric or no such program."""
    try:
        from trustworthy_dl_tpu.obs.registry import get_registry
    except ImportError:
        return []
    metric = get_registry().snapshot()["metrics"].get(name)
    return metric["series"] if metric else []


def _scoped(name: str, scope: str) -> List[float]:
    return [float(s["value"]) for s in _series(name)
            if s["labels"].get("scope") == scope]


def window_pairs() -> List[float]:
    """Pairs each held expert took over the window, all layers."""
    return _scoped(PAIRS, WINDOW)


def window_tokens() -> Optional[float]:
    """Tokens fed through an expert layer over the window, a layer each."""
    values = _scoped(TOKENS, WINDOW)
    return values[0] if values and values[0] > 0 else None


def held_pairs_per_token(run: Any) -> Optional[float]:
    pairs, tokens = window_pairs(), window_tokens()
    if not pairs or tokens is None:
        return None
    return sum(pairs) / tokens


def expert_load_max_x(run: Any) -> Optional[float]:
    pairs = window_pairs()
    if not pairs or sum(pairs) <= 0:
        return None
    return max(pairs) * len(pairs) / sum(pairs)


def recurrent_state_gb(run: Any) -> Optional[float]:
    held = [float(s["value"]) for s in _series(STATE_BYTES) if s["value"]]
    return max(held) / 1e9 if held else None
