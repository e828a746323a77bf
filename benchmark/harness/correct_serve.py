"""What decides ``correct`` in a serving cell.

For a sample of the requests the window finished, the plain reference of
the configuration's family (``families/<model_type>.py``) runs
ONE full forward over each prompt with the reply the timed path served, and
reads at each reply position how far the served token's logit lies below the
reference's largest, in units of the standard deviation of that position's
logits:

* ``worst_shortfall``: the largest over every position of every sampled
  request;
* ``mean_shortfall``: the mean over them;
* ``argmax_miss_share``: the share of positions whose served token is not
  the reference's first choice (with random weights the first two logits
  lie close, so rounding flips some; a fault flips many).

Valid for greedy tokens only, which is what the serving mixes send.
"""

from __future__ import annotations

import sys
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from benchmark.harness import families
from benchmark.harness.traffic import rng_for

Served = Tuple[np.ndarray, Sequence[int]]       # prompt ids, reply tokens


def sample(finished: List[Any], seed: int, count: int) -> List[Any]:
    """``count`` of the finished requests (all where there are fewer): the
    longest, and the rest spread evenly over the others by length, from an
    offset drawn from the seed."""
    ranked = sorted(finished, key=lambda f: (
        len(f.prompt) + len(f.result_tokens), f.index))
    if len(ranked) <= count:
        return ranked
    if count < 2:
        return ranked[len(ranked) - count:]
    rest, picked = ranked[:-1], [ranked[-1]]
    stride = len(rest) / (count - 1)
    offset = float(rng_for(seed, "serve-sample").random()) * stride
    picked += [rest[int(offset + i * stride)] for i in range(count - 1)]
    return picked


def shortfalls(logits: Any, tokens: Sequence[int]) -> Tuple[np.ndarray,
                                                            np.ndarray]:
    """Per position of ``logits`` [n, V]: (largest logit - the logit of
    ``tokens[i]``) / that position's standard deviation, and whether the
    token is not the first choice."""
    import jax.numpy as jnp

    tokens = jnp.asarray(np.asarray(tokens, np.int32))
    served = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    gap = (jnp.max(logits, axis=-1) - served) / jnp.std(logits, axis=-1)
    missed = jnp.argmax(logits, axis=-1) != tokens
    return np.asarray(gap, np.float64), np.asarray(missed)


def numbers(gaps: Sequence[np.ndarray], missed: Sequence[np.ndarray]
            ) -> Dict[str, float]:
    if not gaps:
        return {name: float("nan") for name in (
            "worst_shortfall", "mean_shortfall", "argmax_miss_share")}
    gap, miss = np.concatenate(gaps), np.concatenate(missed)
    return {"worst_shortfall": float(np.max(gap)),
            "mean_shortfall": float(np.mean(gap)),
            "argmax_miss_share": float(np.mean(miss))}


def readings(seed: int, config: Dict[str, Any], served: Sequence[Served],
             max_reply: int, precision: str = "f32", fault: str = "",
             chunk: int = 64) -> Dict[str, float]:
    """The three numbers of ``served`` against the reference made from the
    seed, one request at a time, for the configuration's file ``config``.
    With a lower ``precision`` or a ``fault`` the served replies are first
    replaced by what that control would have served at each position of the
    same prompts and tokens (the reference put in the program's place)."""
    ref = families.of(config)
    params = ref.make_weights(seed, config)
    broken = ref.planted(params, fault, config)
    gaps, missed = [], []
    for i, (prompt, reply) in enumerate(served):
        prompt = np.asarray(prompt, np.int32)
        logits = ref.reply_logits(params, prompt, reply, config, max_reply)
        if precision != "f32" or fault:
            neighbour = np.asarray(served[(i + 1) % len(served)][0])
            context = ref.faulty_context(fault, prompt, neighbour, chunk)
            reply = ref.chosen_tokens(ref.reply_logits(
                broken, context, reply, config, max_reply, precision))
        gap, miss = shortfalls(logits, reply)
        gaps.append(gap)
        missed.append(miss)
    out = numbers(gaps, missed)
    out["compared_requests"] = len(gaps)
    out["compared_tokens"] = int(sum(len(g) for g in gaps))
    return out


def judge(run: Any, found: Dict[str, float], limits: Dict[str, Any]) -> None:
    """Fill ``run.compare``: each number beside its limit.  A number with no
    entry in the cell's file is a fault of the file; one whose entry is
    ``null`` has no upper reading there and is not compared (PERF.md).  No
    finished request to compare reads NaN, which is not correct."""
    run.counters["compared_requests"] = found.pop("compared_requests", 0)
    run.counters["compared_tokens"] = found.pop("compared_tokens", 0)
    for name, value in found.items():
        if name not in limits:
            raise KeyError(f"no limit for {name!r} in the cell's limits")
        if limits[name] is not None:
            run.compare[name] = (value, float(limits[name]))
        else:
            print(f"not compared {name}: {value!r}", file=sys.stderr)
