"""Native ops tier: the Pallas fused moment battery must agree exactly with
the XLA reference reductions (detect/stats.py) — on CPU the kernel runs in
interpreter mode, same code path the TPU compiles."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trustworthy_dl_tpu.detect import stats as st
from trustworthy_dl_tpu.ops.fused_stats import (
    BLOCK_ROWS,
    LANES,
    _xla_moments,
    fused_moments,
)

CHUNK = BLOCK_ROWS * LANES


@pytest.mark.parametrize(
    "n",
    [0, 7, 1000, CHUNK, CHUNK + 1, 2 * CHUNK + 12345],
    ids=["empty", "tiny", "small", "aligned", "aligned+1", "large-ragged"],
)
def test_fused_moments_matches_xla(n):
    x = jax.random.normal(jax.random.PRNGKey(n or 1), (n,), jnp.float32) * 3.0
    got = fused_moments(x, interpret=True)
    ref = _xla_moments(x)
    for g, r in zip(got, ref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-5, atol=1e-4)


def test_fused_moments_propagates_nonfinite():
    """The verifier derives its finite flag from s1/s2 — a NaN anywhere in
    the tensor must reach the sums."""
    x = jnp.ones((CHUNK + 5,), jnp.float32).at[123].set(jnp.nan)
    s1, s2, *_ = fused_moments(x, interpret=True)
    assert not np.isfinite(np.asarray(s1))
    assert not np.isfinite(np.asarray(s2))


def test_fused_moments_under_vmap():
    """The engine calls the battery inside a vmap over the node axis."""
    x = jax.random.normal(jax.random.PRNGKey(3), (4, CHUNK), jnp.float32)
    got = jax.vmap(lambda v: jnp.stack(fused_moments(v, interpret=True)))(x)
    ref = jnp.stack([jnp.stack(_xla_moments(v)) for v in x])
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_leafwise_statistics_with_pallas_path(monkeypatch):
    """Flipping the kernel on must not change the 17-stat battery."""
    leaves = [
        jax.random.normal(jax.random.PRNGKey(7), (CHUNK + 321,), jnp.float32),
        jax.random.normal(jax.random.PRNGKey(8), (513,), jnp.float32),
    ]
    monkeypatch.setenv("TDDL_FUSED_STATS", "0")
    ref_stats, ref_norms, ref_finite, _ = st.leafwise_statistics(leaves)
    monkeypatch.setenv("TDDL_FUSED_STATS", "1")
    got_stats, got_norms, got_finite, _ = st.leafwise_statistics(leaves)
    np.testing.assert_allclose(np.asarray(got_stats), np.asarray(ref_stats),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_norms), np.asarray(ref_norms),
                               rtol=1e-5)
    assert bool(got_finite) == bool(ref_finite)


def test_fused_moments_under_value_and_grad():
    """Regression: the battery runs on param-dependent activations INSIDE
    the engine's value_and_grad; pallas_call has no JVP rule, so without
    the zero-tangent contract the trace asserts (only at sizes that
    engage the kernel — small inputs fall back to XLA and hid this).
    Gradients must also be IDENTICALLY zero through the battery on every
    path (kernel head, XLA tail, small-input fallback), not flip with
    input size."""
    # Kernel-engaging size plus a ragged tail exercising the XLA path too.
    x = jax.random.normal(jax.random.PRNGKey(3),
                          (BLOCK_ROWS * 128 * 2 + 7,), jnp.float32)

    def f(w):
        y = x * w
        return jnp.sum(y ** 2), fused_moments(y)

    (loss, stats), g = jax.value_and_grad(f, has_aux=True)(1.5)
    ref = _xla_moments(x * 1.5)
    for a, b in zip(stats, ref):
        np.testing.assert_allclose(float(a), float(b), rtol=1e-5)
    # Gradient of the actual loss is untouched by the constant battery.
    np.testing.assert_allclose(float(g), float(2 * 1.5 * jnp.sum(x * x)),
                               rtol=1e-5)
    # The battery itself is constant under differentiation on all paths.
    gm = jax.grad(lambda w: fused_moments(x * w)[0])(1.5)
    assert float(gm) == 0.0


def test_partitioned_program_takes_no_mosaic_kernel(monkeypatch):
    """A compiled Mosaic kernel cannot sit in a program GSPMD partitions,
    so the default of every kernel dispatch asks how the program being
    traced will be compiled: ``for_mesh`` over more than one device turns
    the kernels off for the trace (and only for it); one device keeps
    them; the env override still wins (interpret-mode tests partition
    fine)."""
    import numpy as np
    from jax.sharding import Mesh

    from trustworthy_dl_tpu import ops
    from trustworthy_dl_tpu.models import gpt2

    monkeypatch.delenv("TDDL_FUSED_STATS", raising=False)
    assert not ops.mosaic_dispatchable()              # CPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.mosaic_dispatchable() and ops.pallas_enabled()

    def probe():
        return (ops.mosaic_dispatchable(), ops.pallas_enabled(),
                gpt2.auto_picks_flash(gpt2.AUTO_FLASH_MIN_T, 64))

    one = Mesh(np.array(jax.devices()[:1]), ("data",))
    four = Mesh(np.array(jax.devices()[:4]), ("data",))
    assert ops.for_mesh(probe, one)() == (True, True, True)
    assert ops.for_mesh(probe, four)() == (False, False, False)
    assert probe() == (True, True, True)              # the trace is over
    # Under jit the wrapper's body runs while tracing — where it matters.
    seen = []
    jax.jit(ops.for_mesh(lambda x: seen.append(probe()) or x, four))(1.0)
    assert seen == [(False, False, False)]
    monkeypatch.setenv("TDDL_FUSED_STATS", "1")
    assert ops.for_mesh(ops.pallas_enabled, four)()
