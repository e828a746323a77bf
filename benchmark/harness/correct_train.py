"""What decides ``correct`` in a training cell.

The program's first ``proof_steps`` steps, taken through the window's own
call and feed, against the plain reference of the configuration's family
(``families/<model_type>.py``) following the same steps from the same
weights and batches:

* ``loss<i>``: |program - reference| / |reference| of each step's loss;
* ``grad_norm_gap``: the first gradient as the optimizer got it (Adam's
  first moment after one step, over 1 - b1), worst leaf: the gap between
  the program's norm and the reference's, against the reference's norm of
  that leaf or of the median leaf, whichever is larger;
* ``param_change_gap``: the same measure on the norm of each leaf's change
  after the steps, over the leaves whose reference gradient is at least a
  thousandth of the median leaf's (the others move under Adam by round-off
  alone).
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np

from benchmark.harness import families

#: Leaves under this share of the median leaf's gradient norm are left out
#: of ``param_change_gap`` (a rule on the reference's gradient, not a name).
NEGLIGIBLE_GRADIENT = 1e-3


def worst_leaf_gap(program: Sequence[float], reference: Sequence[float],
                   keep: Any = None) -> float:
    program, reference = np.asarray(program, np.float64), np.asarray(
        reference, np.float64)
    scale = np.maximum(reference, np.median(reference))
    gap = np.abs(program - reference) / scale
    if keep is not None:
        gap = gap[np.asarray(keep)]
    return float(np.max(gap))


def numbers(program: Dict[str, Any], reference: Dict[str, Any]
            ) -> Dict[str, float]:
    """``program`` and ``reference``: ``losses`` (one a step), ``grad_norms``
    and ``change_norms`` (one a comparison leaf, same order)."""
    out: Dict[str, float] = {}
    if len(program["losses"]) != len(reference["losses"]):
        raise ValueError("program and reference took different step counts")
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"])):
        out[f"loss{i + 1}"] = abs(a - b) / abs(b)
    ref_g = np.asarray(reference["grad_norms"], np.float64)
    out["grad_norm_gap"] = worst_leaf_gap(program["grad_norms"], ref_g)
    moved = ref_g >= NEGLIGIBLE_GRADIENT * np.median(ref_g)
    out["param_change_gap"] = worst_leaf_gap(
        program["change_norms"], reference["change_norms"], keep=moved)
    return out


def reference_readings(seed: int, config: Dict[str, Any],
                       batches: List[Dict[str, Any]], opt: Dict[str, float],
                       precision: str = "f32", rows: int = 2,
                       fault: str = "") -> Dict[str, Any]:
    """The reference's side of ``numbers`` (or, at a lower ``precision`` or
    with a ``fault``, a control's), for the configuration's file
    ``config``."""
    import jax

    ref = families.of(config)
    p0 = ref.make_weights(seed, config)
    out = ref.train_steps(p0, batches, config, opt, precision, rows,
                          fault=fault)
    change = jax.tree_util.tree_map(lambda a, b: a - b, out["params"], p0)
    return {
        "losses": out["losses"],
        "grad_norms": np.asarray(ref.leaf_norms(out["first_grads"])),
        "change_norms": np.asarray(ref.leaf_norms(change)),
    }


def judge(run: Any, program: Dict[str, Any], reference: Dict[str, Any],
          limits: Dict[str, float]) -> None:
    """Fill ``run.compare``: each number beside its limit.  A number with
    no entry in the cell's file is a fault of the file; one whose entry is
    ``null`` is not compared in that cell (no control or fault gave it an
    upper reading there, so a limit could only fail sound runs; PERF.md)."""
    for name, value in numbers(program, reference).items():
        if name not in limits:
            raise KeyError(f"no limit for {name!r} in the cell's limits")
        if limits[name] is not None:
            run.compare[name] = (value, float(limits[name]))
