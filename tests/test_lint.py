"""tddl-lint (trustworthy_dl_tpu/analysis/): the AST invariant linter.

Three layers, all host-only and fast-tier (``lint`` marker):

* **Fixture drills per rule family** — a positive (seeded violation →
  finding with the right file:line), a negative (idiomatic code →
  clean), and where it matters the regex-ancestor's blind spot the AST
  rule must close (multi-line emits, comprehension-scoped names).
* **Engine mechanics** — inline/file suppressions, baseline round-trip
  (grandfather → clean → stale detection), parse-error containment,
  CLI exit codes and formats.
* **THE tier-1 gate** — the full default rule set over the REAL repo
  with the committed baseline must be clean; this is the test that
  turns every contract above into a merge blocker.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from trustworthy_dl_tpu.analysis import (LintConfig, LintEngine,
                                         all_rules, load_baseline,
                                         run_lint, write_baseline)
from trustworthy_dl_tpu.analysis.cli import main as lint_main

pytestmark = pytest.mark.lint

REPO = Path(__file__).resolve().parent.parent

#: Synthetic event vocabulary so fixtures don't depend on the real enum.
EVENTS = frozenset({"TRAIN_STEP", "SERVE_RETIRE"})


def _write_tree(tmp_path, files):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))


def _run(tmp_path, files, config=None, rules=None, baseline=None):
    _write_tree(tmp_path, files)
    engine = LintEngine(
        all_rules(),
        config=config or LintConfig(event_members=EVENTS))
    return engine.run(str(tmp_path), paths=[str(tmp_path)],
                      rule_names=rules, baseline=baseline)


def _rules_of(result):
    return sorted({f.rule for f in result.findings})


# ---------------------------------------------------------------------------
# obs contracts
# ---------------------------------------------------------------------------


def test_obs_emit_rule_catches_raw_strings_typos_and_multiline_calls(
        tmp_path):
    result = _run(tmp_path, {
        "trustworthy_dl_tpu/mod.py": '''\
            def f(bus, EventType):
                bus.emit("train_step", step=1)          # raw string
                bus.emit(EventType.NOPE, step=1)        # typo'd member
                bus.emit(EventType.TRAIN_STEP, step=1)  # fine
                bus.emit(                               # multi-line: the
                    "serve_retire", request_id=1)       # regex blind spot
            ''',
    }, rules=["obs-emit-type"])
    lines = sorted(f.line for f in result.findings)
    assert lines == [2, 3, 6]
    assert all(f.path == "trustworthy_dl_tpu/mod.py"
               for f in result.findings)
    assert "raw" not in result.findings[0].message  # message names the arg
    assert "'train_step'" in result.findings[0].message
    assert "EventType.NOPE" in result.findings[1].message


def test_metric_prefix_rule_literals_fstrings_and_wrapper(tmp_path):
    result = _run(tmp_path, {
        "trustworthy_dl_tpu/mod.py": '''\
            def f(reg, _metric, name):
                reg.counter("bad_total")                 # missing prefix
                reg.gauge(f"bad_{name}_depth")           # f-string head
                reg.histogram("tddl_ok_seconds")         # fine
                reg.counter(f"tddl_{name}_total")        # fine (head ok)
                reg.counter(name)                        # dynamic: skipped
                _metric(reg.counter, "bad_wrapped_total", "help")
                _metric(reg.counter, "tddl_wrapped_total", "help")
            ''',
    }, rules=["metric-prefix"])
    assert sorted(f.line for f in result.findings) == [2, 3, 7]


def test_metric_label_vocab_rule(tmp_path):
    result = _run(tmp_path, {
        "trustworthy_dl_tpu/mod.py": '''\
            def f(reg, dyn):
                reg.counter("tddl_a_total", labels=("tenant",))   # known
                reg.counter("tddl_b_total", labels=("tenent",))   # typo!
                reg.gauge("tddl_c", labels=("status",) + dyn)     # mixed
            ''',
    }, rules=["metric-label-vocab"])
    assert [f.line for f in result.findings] == [3]
    assert "'tenent'" in result.findings[0].message


# ---------------------------------------------------------------------------
# resource locality
# ---------------------------------------------------------------------------


def test_adapter_locality_rule_flags_forked_spellings(tmp_path):
    # The adapter page-table row and pool PartitionSpecs have ONE home
    # (serve/adapters.py): a redefinition elsewhere, or an ad-hoc
    # PartitionSpec inside an adapter-handling function, forks the
    # compile-once pin.  Calling the imported home spelling is fine.
    result = _run(tmp_path, {
        "trustworthy_dl_tpu/serve/scheduler.py": '''\
            from jax.sharding import PartitionSpec as P
            from trustworthy_dl_tpu.serve.adapters import adapter_page_row

            def adapter_page_row(slots, n):          # forked spelling
                return [0] * n

            def _shard_adapter_pool(arrs):           # ad-hoc adapter spec
                return P("data")

            def _shard_kv_pool(arrs):                # non-adapter: fine
                return P("data")

            def admit(task, n):
                return adapter_page_row({}, n)       # calling home: fine
            ''',
    }, rules=["adapter-locality"])
    assert sorted(f.line for f in result.findings) == [4, 8]
    assert "one spelling" in result.findings[0].message


def test_adapter_locality_rule_home_module_and_suppression_clean(tmp_path):
    # The home module itself is exempt; elsewhere an inline suppression
    # with a justification comment silences a deliberate exception.
    result = _run(tmp_path, {
        "trustworthy_dl_tpu/serve/adapters.py": '''\
            from jax.sharding import PartitionSpec

            def adapter_partition_specs():
                return PartitionSpec(), PartitionSpec()
            ''',
        "trustworthy_dl_tpu/serve/engine.py": '''\
            from jax.sharding import PartitionSpec as P

            def _resize_adapter_pool(arrs):
                # tddl-lint: disable=adapter-locality — test fixture
                return P()
            ''',
    }, rules=["adapter-locality"])
    assert result.findings == []


def test_sharding_registry_rule_flags_specs_outside_home(tmp_path):
    # PR 19: PartitionSpec has ONE spelling site — the logical-axis
    # registry (core/sharding.py).  Direct calls, ``as P`` aliases, and
    # attribute spellings elsewhere are all findings; calling the
    # registry's helpers is the sanctioned path.
    result = _run(tmp_path, {
        "trustworthy_dl_tpu/engine/trainer.py": '''\
            import jax.sharding
            from jax.sharding import PartitionSpec as P
            from trustworthy_dl_tpu.core import sharding as shreg

            def place(mesh):
                a = P("data")                            # aliased ctor
                b = jax.sharding.PartitionSpec("model")  # attr spelling
                c = shreg.replicated_spec()              # registry: fine
                return a, b, c
            ''',
    }, rules=["sharding-registry-only"])
    assert sorted(f.line for f in result.findings) == [6, 7]
    assert "logical-axis registry" in result.findings[0].message


def test_sharding_registry_rule_home_whitelist_and_suppression(tmp_path):
    # The registry itself and whitelisted modules (adapter home) are
    # exempt; elsewhere a justified inline suppression still works, and
    # test trees outside the package are out of scope entirely.
    result = _run(tmp_path, {
        "trustworthy_dl_tpu/core/sharding.py": '''\
            from jax.sharding import PartitionSpec

            def replicated_spec():
                return PartitionSpec()
            ''',
        "trustworthy_dl_tpu/serve/adapters.py": '''\
            from jax.sharding import PartitionSpec

            def adapter_partition_specs():
                return PartitionSpec(), PartitionSpec()
            ''',
        "trustworthy_dl_tpu/serve/engine.py": '''\
            from jax.sharding import PartitionSpec as P

            def special_case():
                # tddl-lint: disable=sharding-registry-only — fixture
                return P()
            ''',
        "tests/test_something.py": '''\
            from jax.sharding import PartitionSpec

            def test_spec():
                assert PartitionSpec() is not None
            ''',
    }, rules=["sharding-registry-only"])
    assert result.findings == []


# ---------------------------------------------------------------------------
# determinism
# ---------------------------------------------------------------------------


def test_tick_determinism_rule(tmp_path):
    # The fixture lives AT a real deterministic-module path so the
    # default contract table scopes onto it.
    result = _run(tmp_path, {
        "trustworthy_dl_tpu/serve/control.py": '''\
            import random, time
            import numpy as np

            def decide(seed, ticks):
                t = time.time()                      # wall clock
                r = random.random()                  # global RNG
                x = np.random.rand()                 # global numpy RNG
                bad = np.random.default_rng()        # unseeded
                rng = np.random.default_rng(seed)    # fine
                for k in {1, 2}:                     # set iteration
                    pass
                for k in sorted({1, 2}):             # fine: sorted
                    pass
                return t + r + x
            ''',
        "trustworthy_dl_tpu/other.py": '''\
            import time

            def fine():
                return time.time()   # not a deterministic module
            ''',
    }, rules=["tick-determinism"])
    assert sorted(f.line for f in result.findings) == [5, 6, 7, 8, 10]
    assert all(f.path.endswith("control.py") for f in result.findings)


def test_predict_purity_rule_and_regression_fixture(tmp_path):
    # Regression fixture mirroring the REAL pinned surface: an
    # autoscale_pressure/predict_fleet pair that sneaks in a module
    # -global mutable cache would silently make drill pins depend on
    # call history.
    result = _run(tmp_path, {
        "trustworthy_dl_tpu/serve/mod.py": '''\
            _CACHE = {}
            HISTORY = []
            LIMITS = (1, 2)            # immutable: fine to read

            def autoscale_pressure(cfg, sig):
                if sig in _CACHE:      # read of mutable global
                    return _CACHE[sig]
                return LIMITS[0]

            def predict_fleet(plan, horizon):
                global HISTORY         # impure declaration
                HISTORY.append(horizon)
                return horizon

            def predict_local_ok(cfg, _CACHE):
                return _CACHE          # shadowed by a parameter

            def helper_reads_cache():
                return _CACHE          # not a prediction function
            ''',
    }, rules=["predict-purity"])
    msgs = {(f.line, f.rule) for f in result.findings}
    by_line = sorted(f.line for f in result.findings)
    # _CACHE read twice in autoscale_pressure (lines 6, 7), the global
    # declaration (11) and its HISTORY use (12).
    assert by_line == [6, 7, 11, 12], result.findings
    assert any("global" in f.message for f in result.findings)
    assert any("_CACHE" in f.message for f in result.findings)
    assert msgs  # noqa: keep flake quiet about the helper var


# ---------------------------------------------------------------------------
# import purity
# ---------------------------------------------------------------------------


def test_import_purity_transitive_chain_and_lazy_escape(tmp_path):
    config = LintConfig(
        event_members=EVENTS,
        host_only_modules=("trustworthy_dl_tpu/hostonly.py",))
    result = _run(tmp_path, {
        "trustworthy_dl_tpu/__init__.py": "",
        "trustworthy_dl_tpu/hostonly.py": '''\
            from typing import TYPE_CHECKING

            from trustworthy_dl_tpu import middle

            if TYPE_CHECKING:
                import jax  # annotation-only: never executes

            def lazy():
                import jax  # sanctioned escape hatch
                return jax
            ''',
        "trustworthy_dl_tpu/middle.py": "import jax.numpy as jnp\n",
    }, config=config, rules=["import-purity"])
    assert len(result.findings) == 1
    f = result.findings[0]
    assert f.path == "trustworthy_dl_tpu/hostonly.py"
    assert f.line == 3                      # the first hop's import
    assert "trustworthy_dl_tpu/middle.py -> jax" in f.message

    # Cutting the chain clears it.
    clean = _run(tmp_path, {
        "trustworthy_dl_tpu/middle.py": "import numpy as np\n",
    }, config=config, rules=["import-purity"])
    assert clean.clean


# ---------------------------------------------------------------------------
# jit hazards
# ---------------------------------------------------------------------------


def test_recompile_hazard_rule(tmp_path):
    result = _run(tmp_path, {
        "trustworthy_dl_tpu/serve/scheduler.py": '''\
            import jax
            import jax.numpy as jnp

            def rebuild(fns):
                for fn in fns:
                    fns[fn] = jax.jit(fn)        # re-jit per iteration

            def decode_tick(self, xs):
                step = jax.jit(lambda a: a + 1)  # cache-key churn
                for x in xs:
                    pad = jnp.array([0, 0])      # literal per iteration
                    y = jnp.asarray(x)           # fine: real data
                return pad, y, step

            def _decode_impl(tokens):
                for _ in range(2):
                    z = jnp.array([1.0])         # fine: traced program
                return z

            def cold_setup():
                for _ in range(2):
                    w = jnp.array([1.0])         # fine: not a hot fn
                return w
            ''',
    }, rules=["recompile-hazard"])
    assert sorted(f.line for f in result.findings) == [6, 9, 11]
    assert any("re-traces" in f.message for f in result.findings)
    assert any("lambda" in f.message for f in result.findings)
    assert any("hoist" in f.message for f in result.findings)


def test_host_sync_rule_taint_comprehension_scope_and_suppression(
        tmp_path):
    result = _run(tmp_path, {
        "trustworthy_dl_tpu/serve/scheduler.py": '''\
            import numpy as np
            import jax.numpy as jnp

            def decode_tick(self, progs, tokens):
                packed = progs["decode"](jnp.asarray(tokens))
                host = np.asarray(packed)           # accidental pull
                ent = float(host[1])                # fine: host value
                loss = float(packed[0])             # accidental pull
                drafts = [np.asarray(d) for d in packed]  # sync in comp
                d = drafts[0]
                tok = int(d[0])                     # fine: host (the
                return ent, loss, tok, d            # scheduler d-case)

            def _spec_tick(self, progs, xs):
                out = progs["draft"](xs)
                # tddl-lint: disable=host-sync — the one deliberate pull
                host = np.asarray(out)
                return host

            def cold_path(progs, xs):
                return np.asarray(progs["x"](xs))   # out of scope
            ''',
    }, rules=["host-sync"])
    assert sorted(f.line for f in result.findings) == [6, 8, 9]
    assert all("decode_tick" in f.message for f in result.findings)


# ---------------------------------------------------------------------------
# hygiene
# ---------------------------------------------------------------------------


def test_mutable_default_rule(tmp_path):
    result = _run(tmp_path, {
        "trustworthy_dl_tpu/mod.py": '''\
            import dataclasses
            from dataclasses import field

            def f(xs=[], m={}, ok=None, t=()):        # two findings
                return xs, m, ok, t

            @dataclasses.dataclass
            class Cfg:
                aux: dict = field(default={})          # finding
                tags: list = []                        # finding
                names: list = field(default_factory=list)  # fine
                k: int = 3                             # fine

            class NotADataclass:
                shared = []                            # fine (class attr)
            ''',
    }, rules=["mutable-default"])
    assert len(result.findings) == 4
    assert {f.line for f in result.findings} == {4, 9, 10}


def test_bare_except_rule_scoped_to_recovery_paths(tmp_path):
    files = {
        "trustworthy_dl_tpu/engine/supervisor.py": '''\
            def recover():
                try:
                    pass
                except:                  # swallows SystemExit
                    pass
                try:
                    pass
                except Exception:        # fine
                    pass
            ''',
        "trustworthy_dl_tpu/models/other.py": '''\
            def f():
                try:
                    pass
                except:                  # out of the rule's scope
                    pass
            ''',
    }
    result = _run(tmp_path, files, rules=["bare-except"])
    assert [(f.path, f.line) for f in result.findings] == \
        [("trustworthy_dl_tpu/engine/supervisor.py", 4)]


def test_artifact_metadata_rule(tmp_path):
    result = _run(tmp_path, {
        "trustworthy_dl_tpu/experiments/a.py": '''\
            import json

            def save(path, payload):
                with open(path + ".tmp", "w") as f:
                    json.dump(payload, f)
                import os
                os.replace(path + ".tmp", path)
            ''',
        "trustworthy_dl_tpu/experiments/b.py": '''\
            import json
            from trustworthy_dl_tpu.obs.meta import run_metadata

            def save(path, payload):
                payload["run_metadata"] = run_metadata()
                with open(path + ".tmp", "w") as f:
                    json.dump(payload, f)
                import os
                os.replace(path + ".tmp", path)
            ''',
        "trustworthy_dl_tpu/experiments/c.py": '''\
            from trustworthy_dl_tpu.utils.io import atomic_write_json

            def save(path, payload):
                atomic_write_json(path, payload)   # atomic but unstamped
            ''',
    }, rules=["artifact-metadata"])
    assert sorted(f.path for f in result.findings) == [
        "trustworthy_dl_tpu/experiments/a.py",
        "trustworthy_dl_tpu/experiments/c.py",
    ]


def test_artifact_reason_vocab_rule(tmp_path):
    # The vocabulary applies at dump/assemble surfaces only, in every
    # literal position those surfaces accept: first positional, the
    # ``reason=`` kwarg, and ``dump()``'s second slot (``FlightRecorder
    # .dump(directory, reason)``).  Dynamic reasons and other callables'
    # ``reason=`` namespaces pass through.
    result = _run(tmp_path, {
        "trustworthy_dl_tpu/serve/mod.py": '''\
            import json

            def episode(session, recorder, fleet, payload, why, obs_dir):
                session.dump_flight("slo_breech", step=3)      # typo
                fleet._forensic_incident(reason="preemption ")  # typo
                recorder.dump(obs_dir, "guard_tripp")          # typo
                session.dump_flight("guard_trip", step=3)      # vocab
                session.dump_flight(why, step=3)          # dynamic: ok
                json.dump(payload, open("/dev/null", "w"))  # not ours
                fleet.schedule(reason="retry_budget")     # other ns
                recorder.dump("smoke_drill")  # tddl-lint: disable=artifact-reason-vocab
            ''',
    }, rules=["artifact-reason-vocab"])
    assert _rules_of(result) == ["artifact-reason-vocab"]
    assert sorted(f.line for f in result.findings) == [4, 5, 6]
    assert "slo_breech" in result.findings[0].message


def test_atomic_write_rule(tmp_path):
    result = _run(tmp_path, {
        "trustworthy_dl_tpu/obs/mod.py": '''\
            import json, os
            from pathlib import Path

            def bad(path, payload):
                with open(path, "w") as f:          # truncates in place
                    json.dump(payload, f)

            def bad_pathlib(path, text):
                Path(path).write_text(text)         # same hazard

            def good(path, payload):
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(payload, f)
                os.replace(tmp, path)

            def append_log(path, line):
                with open(path, "a") as f:          # append: fine
                    f.write(line)
            ''',
    }, rules=["atomic-write"])
    assert sorted(f.line for f in result.findings) == [5, 9]


# ---------------------------------------------------------------------------
# engine mechanics: suppressions, baseline, parse errors
# ---------------------------------------------------------------------------


def test_suppressions_line_block_and_file(tmp_path):
    src_variants = {
        # same-line
        "trustworthy_dl_tpu/a.py":
            'def f(reg):\n'
            '    reg.counter("bad_total")  '
            '# tddl-lint: disable=metric-prefix — legacy export\n',
        # justification block above, disable on its first line
        "trustworthy_dl_tpu/b.py":
            'def f(reg):\n'
            '    # tddl-lint: disable=metric-prefix — kept for the\n'
            '    # external dashboard that predates the convention\n'
            '    reg.counter("bad_total")\n',
        # file-level
        "trustworthy_dl_tpu/c.py":
            '# tddl-lint: disable-file=metric-prefix\n'
            'def f(reg):\n'
            '    reg.counter("bad_total")\n'
            '    reg.counter("also_bad_total")\n',
        # a DIFFERENT rule's suppression must not silence this one
        "trustworthy_dl_tpu/d.py":
            'def f(reg):\n'
            '    reg.counter("bad_total")  '
            '# tddl-lint: disable=host-sync\n',
    }
    result = _run(tmp_path, src_variants, rules=["metric-prefix"])
    assert [f.path for f in result.findings] == ["trustworthy_dl_tpu/d.py"]


def test_baseline_round_trip_and_stale_detection(tmp_path):
    files = {
        "trustworthy_dl_tpu/mod.py":
            'def f(reg):\n    reg.counter("bad_total")\n',
    }
    dirty = _run(tmp_path, files, rules=["metric-prefix"])
    assert len(dirty.findings) == 1

    baseline_path = tmp_path / "baseline.json"
    write_baseline(dirty.findings, str(baseline_path),
                   justification="pre-lint metric kept for dashboards")
    entries = load_baseline(str(baseline_path))
    assert entries[0]["justification"].startswith("pre-lint")

    grandfathered = _run(tmp_path, files, rules=["metric-prefix"],
                         baseline=entries)
    assert grandfathered.clean and grandfathered.baselined == 1
    assert grandfathered.stale_baseline == []

    # Fix the source: the entry goes STALE and is surfaced.
    (tmp_path / "trustworthy_dl_tpu/mod.py").write_text(
        'def f(reg):\n    reg.counter("tddl_good_total")\n')
    fixed = _run(tmp_path, files={}, rules=["metric-prefix"],
                 baseline=entries)
    assert fixed.clean and fixed.baselined == 0
    assert len(fixed.stale_baseline) == 1

    # A justification-free entry is refused at load.
    bad = {"version": 1, "findings": [
        {"rule": "metric-prefix", "path": "x.py", "message": "m"}]}
    (tmp_path / "bad.json").write_text(json.dumps(bad))
    with pytest.raises(ValueError, match="justification"):
        load_baseline(str(tmp_path / "bad.json"))


def test_parse_error_is_a_finding_not_a_crash(tmp_path):
    result = _run(tmp_path, {
        "trustworthy_dl_tpu/broken.py": "def f(:\n",
        "trustworthy_dl_tpu/fine.py": "x = 1\n",
    })
    assert [f.rule for f in result.findings] == ["parse-error"]
    assert result.files_scanned == 2


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _seeded_violation_tree():
    """One violation per rule family (the acceptance-criteria drill)."""
    return {
        "trustworthy_dl_tpu/obs/mod.py": '''\
            import json

            def f(bus, reg, path, payload):
                bus.emit("train_step", step=1)
                reg.counter("bad_total", labels=("tenent",))
                with open(path, "w") as f:
                    json.dump(payload, f)
            ''',
        "trustworthy_dl_tpu/experiments/writer.py": '''\
            import json

            def save(path, payload):
                with open(path + ".tmp", "w") as f:
                    json.dump(payload, f)
                import os
                os.replace(path + ".tmp", path)
            ''',
        "trustworthy_dl_tpu/serve/control.py": '''\
            import time

            def decide():
                return time.time()
            ''',
        "trustworthy_dl_tpu/serve/scheduler.py": '''\
            import numpy as np
            import jax
            import jax.numpy as jnp

            def decode_tick(self, progs, xs):
                for x in xs:
                    pad = jnp.array([0])
                step = jax.jit(lambda a: a)
                out = progs["d"](xs)
                return np.asarray(out), pad, step

            def f(xs=[]):
                try:
                    return xs
                except:
                    pass
            ''',
        "trustworthy_dl_tpu/obs/sentinel.py": "import jax\n",
        "trustworthy_dl_tpu/engine/supervisor.py": '''\
            def recover():
                try:
                    pass
                except:
                    pass
            ''',
    }


def test_cli_seeded_violations_exit_nonzero_with_locations(tmp_path,
                                                           capsys):
    _write_tree(tmp_path, _seeded_violation_tree())
    rc = lint_main(["--root", str(tmp_path), str(tmp_path),
                    "--no-baseline"])
    out = capsys.readouterr().out
    assert rc == 1
    expected = {
        "obs-emit-type": "trustworthy_dl_tpu/obs/mod.py:4",
        "metric-prefix": "trustworthy_dl_tpu/obs/mod.py:5",
        "metric-label-vocab": "trustworthy_dl_tpu/obs/mod.py:5",
        "atomic-write": "trustworthy_dl_tpu/obs/mod.py:6",
        "artifact-metadata": "trustworthy_dl_tpu/experiments/writer.py:5",
        "tick-determinism": "trustworthy_dl_tpu/serve/control.py:4",
        "recompile-hazard": "trustworthy_dl_tpu/serve/scheduler.py:7",
        "host-sync": "trustworthy_dl_tpu/serve/scheduler.py:10",
        "mutable-default": "trustworthy_dl_tpu/serve/scheduler.py:12",
        "bare-except": "trustworthy_dl_tpu/engine/supervisor.py:4",
        "import-purity": "trustworthy_dl_tpu/obs/sentinel.py:1",
    }
    for rule, location in expected.items():
        assert f"{location}: [{rule}]" in out, (rule, out)


def test_cli_formats_filters_and_exit_codes(tmp_path, capsys):
    _write_tree(tmp_path, {
        "trustworthy_dl_tpu/mod.py":
            'def f(reg):\n    reg.counter("bad_total")\n'})

    # Clean when the only violating rule is filtered out.
    rc = lint_main(["--root", str(tmp_path), str(tmp_path),
                    "--no-baseline", "--rules", "obs-emit-type"])
    assert rc == 0
    capsys.readouterr()

    # JSON format carries the structured payload.
    rc = lint_main(["--root", str(tmp_path), str(tmp_path),
                    "--no-baseline", "--format", "json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1 and payload["clean"] is False
    assert payload["by_rule"] == {"metric-prefix": 1}
    assert payload["findings"][0]["line"] == 2

    # Unknown rule name: usage error.
    rc = lint_main(["--root", str(tmp_path), str(tmp_path),
                    "--rules", "nonsense"])
    assert rc == 2
    assert "unknown rule" in capsys.readouterr().err

    # --list-rules names every shipped rule.
    rc = lint_main(["--list-rules"])
    out = capsys.readouterr().out
    assert rc == 0
    for rule in all_rules():
        assert rule.name in out

    # --write-baseline grandfathers (default scan from --root — it
    # REFUSES --rules/path filters, which would silently drop every
    # other entry), then the default run is clean and rc 0.
    baseline = tmp_path / "base.json"
    rc = lint_main(["--root", str(tmp_path), str(tmp_path),
                    "--write-baseline", "--baseline", str(baseline)])
    assert rc == 2 and not baseline.exists()   # path filter refused
    assert "--write-baseline" in capsys.readouterr().err
    rc = lint_main(["--root", str(tmp_path),
                    "--write-baseline", "--baseline", str(baseline)])
    assert rc == 0 and baseline.exists()
    capsys.readouterr()
    rc = lint_main(["--root", str(tmp_path), str(tmp_path),
                    "--baseline", str(baseline)])
    assert rc == 0


# ---------------------------------------------------------------------------
# the real repo: tier-1 gate + self-purity
# ---------------------------------------------------------------------------


def test_repo_is_clean_under_the_committed_baseline():
    """THE gate: full default rule set over the real package and tests
    with the committed baseline — zero findings.  A new
    violation fails HERE, at review time, not in a chaos drill."""
    result = run_lint(root=str(REPO))
    assert result.clean, "\n".join(
        f"{f.location}: [{f.rule}] {f.message}" for f in result.findings)
    # Stale entries mean the baseline should shrink — keep it honest.
    assert result.stale_baseline == [], result.stale_baseline
    assert result.files_scanned > 100


def test_committed_baseline_loads_and_is_justified():
    path = REPO / "tddl_lint_baseline.json"
    entries = load_baseline(str(path))   # raises on missing justification
    assert isinstance(entries, list)


def test_lint_cli_process_is_jax_free():
    """The console entry's own contract: a full lint run in a fresh
    process never imports jax (so it works when the backend is the
    broken thing).  sys.modules is the ground truth the import-purity
    rule approximates statically."""
    code = (
        "import sys\n"
        "from trustworthy_dl_tpu.analysis.cli import main\n"
        "rc = main(['-q'])\n"
        "assert rc == 0, rc\n"
        "bad = [m for m in sys.modules if m.split('.')[0] in "
        "('jax', 'jaxlib')]\n"
        "assert not bad, bad\n"
        "print('ok')\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=str(REPO),
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "ok" in proc.stdout

