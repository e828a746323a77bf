"""tddl-lint — AST-based invariant linter for the tddl codebase.

Thirteen PRs of trustworthy-serving work accumulated contracts that were
enforced only by convention, by regex scans in ``tests/test_obs.py``, or
by runtime watchers that fire after the damage (PR 10's CompileWatcher
caught a real silent full-step recompile; PR 2 shipped four latent
donation/aliasing heap corruptions).  This package turns those
hard-won contracts into *static* rules that fail at review time:

* **obs contracts** — every ``.emit(`` passes a real ``EventType``
  member; every registered metric literal is ``tddl_``-prefixed and its
  label names come from the known dashboard vocabulary.
* **determinism** — no wall clocks / unseeded RNGs / set-iteration in
  the tick-deterministic modules drills pin exact counts against.
* **import purity** — modules documented host-only must not reach
  ``jax``/``jaxlib`` through any module-level import chain.
* **recompile hazards** — no re-``jit`` inside loops, no
  ``jax.jit(lambda ...)`` cache-key churn, no ``jnp.array`` literals
  built inside hot loops (the PR 10 storm pattern).
* **host-sync hazards** — no ``np.asarray``/``float()``/``.item()`` on
  device values inside the decode tick / ``_train_step`` dispatch.
* **hygiene** — mutable defaults, bare ``except:`` in recovery paths,
  unstamped or non-atomic artifact writes.

Host-only by contract: nothing in this package (or anything it imports
at module level) may import jax — the ``import-purity`` rule lints the
linter itself.

Entry points: the ``trustworthy-dl-lint`` console script
(:mod:`trustworthy_dl_tpu.analysis.cli`) and the tier-1 test perimeter
(``tests/test_lint.py``).
"""

from trustworthy_dl_tpu.analysis.engine import (  # noqa: F401
    Finding,
    LintConfig,
    LintEngine,
    LintResult,
    ModuleInfo,
    Project,
    Rule,
    run_lint,
)
from trustworthy_dl_tpu.analysis.baseline import (  # noqa: F401
    load_baseline,
    write_baseline,
)
from trustworthy_dl_tpu.analysis.rules import all_rules  # noqa: F401

__all__ = [
    "Finding", "LintConfig", "LintEngine", "LintResult", "ModuleInfo",
    "Project", "Rule", "all_rules", "load_baseline", "run_lint",
    "write_baseline",
]
