"""Pallas flash attention vs the XLA reference (models/gpt2.full_attention).

The kernel recomputes softmax blockwise from saved row-logsumexps; these
tests pin forward AND backward equality (causal and not), tail/fallback
behavior, and the end-to-end GPT-2 path under ``attn_impl='flash'``.
Interpret mode on the CPU backend — the same kernel compiles for TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trustworthy_dl_tpu.models import gpt2
from trustworthy_dl_tpu.models.gpt2 import GPT2Config, full_attention
from trustworthy_dl_tpu.ops.flash_attention import (
    _block_for,
    _blocks_for,
    flash_attention,
    flash_chunk,
    scheduled_pairs,
    scheduled_sub_tiles,
)

B, H, T, D = 2, 4, 128, 32


@pytest.fixture(scope="module")
def qkv():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    return tuple(jax.random.normal(k, (B, H, T, D), jnp.float32) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_forward_matches_full(qkv, causal):
    q, k, v = qkv
    ref = full_attention(q, k, v, causal)
    got = jax.jit(flash_attention, static_argnums=3)(q, k, v, causal)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-5
    )


@pytest.mark.parametrize("causal", [True, False])
def test_flash_grads_match_full(qkv, causal):
    q, k, v = qkv

    def scalar(fn):
        # Nonuniform cotangent so transpose errors can't cancel.
        w = jnp.arange(T, dtype=jnp.float32)[None, None, :, None] / T
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal) * w)

    ref = jax.grad(scalar(full_attention), argnums=(0, 1, 2))(q, k, v)
    got = jax.jit(jax.grad(scalar(flash_attention), argnums=(0, 1, 2)))(
        q, k, v
    )
    for g, r in zip(got, ref):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=5e-4, atol=5e-5
        )


def test_flash_multiblock_grid():
    """T spanning several 64-wide blocks exercises the online-softmax
    accumulator and the causal tile-skip across grid steps."""
    t = 192  # 3 blocks of 64
    assert _block_for(t) == 64
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(kk, (1, 2, t, 16), jnp.float32) for kk in ks)
    ref = full_attention(q, k, v, True)
    got = flash_attention(q, k, v, True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-5
    )


def test_flash_bf16_inputs(qkv):
    q, k, v = (a.astype(jnp.bfloat16) for a in qkv)
    ref = full_attention(q, k, v, True)
    got = flash_attention(q, k, v, True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_flash_odd_length_falls_back(qkv):
    """T=100 doesn't tile: must silently use the XLA path, same numbers."""
    q, k, v = (a[:, :, :100] for a in qkv)
    ref = full_attention(q, k, v, True)
    got = flash_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)


def test_gpt2_flash_end_to_end():
    """Loss and parameter grads of a tiny GPT-2 under attn_impl='flash'
    match the full-attention baseline."""
    base = GPT2Config(vocab_size=96, n_positions=T, n_layer=2, n_embd=64,
                      n_head=4, dtype=jnp.float32, attn_impl="full")
    flash = GPT2Config(**{**base.__dict__, "attn_impl": "flash"})
    params = gpt2.init_params(jax.random.PRNGKey(0), base)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, 96)
    batch = {"input": tokens, "target": jnp.roll(tokens, -1, axis=-1)}

    ref_loss, ref_grads = jax.value_and_grad(gpt2.loss_fn)(params, batch, base)
    got_loss, got_grads = jax.jit(
        jax.value_and_grad(gpt2.loss_fn), static_argnums=2
    )(params, batch, flash)

    assert float(got_loss) == pytest.approx(float(ref_loss), rel=1e-4)
    for g, r in zip(jax.tree_util.tree_leaves(got_grads),
                    jax.tree_util.tree_leaves(ref_grads)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=2e-3, atol=2e-4
        )


def test_auto_attention_dispatch(monkeypatch):
    """attn_impl='auto': XLA path below AUTO_FLASH_MIN_T, flash kernel at
    long T on the TPU backend (off-TPU auto always takes the XLA path —
    interpret-mode Pallas is test-only territory) — numerics match full
    attention in every case.  The flash branch is exercised here too by
    faking the backend check, so a dispatch bug cannot hide until real
    TPU hardware."""
    from trustworthy_dl_tpu.models import gpt2 as g

    auto = g.get_attention("auto")
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    for t in (64, g.AUTO_FLASH_MIN_T):
        q, k, v = (jax.random.normal(kk, (1, 2, t, 32), jnp.float32)
                   for kk in ks)
        np.testing.assert_allclose(
            np.asarray(auto(q, k, v, True)),
            np.asarray(g.full_attention(q, k, v, True)),
            rtol=2e-4, atol=2e-5,
        )
    # Predicate truth table on this (CPU) backend, then force "tpu" so the
    # flash branch really runs and still matches.  The kernel itself must
    # keep interpret mode (we are still on CPU), so pin _interpret before
    # faking the backend — both read jax.default_backend.
    import importlib

    # ops/__init__ re-exports the flash_attention FUNCTION under the
    # submodule's name, shadowing it as a package attribute — resolve the
    # module itself.
    fa = importlib.import_module("trustworthy_dl_tpu.ops.flash_attention")

    assert not g.auto_picks_flash(g.AUTO_FLASH_MIN_T, 32)
    monkeypatch.setattr(fa, "_interpret", lambda: True)
    monkeypatch.setattr(g.jax, "default_backend", lambda: "tpu")
    assert g.auto_picks_flash(g.AUTO_FLASH_MIN_T, 32)
    assert not g.auto_picks_flash(64, 32)
    t = g.AUTO_FLASH_MIN_T
    q, k, v = (jax.random.normal(kk, (1, 2, t, 32), jnp.float32)
               for kk in ks)
    np.testing.assert_allclose(
        np.asarray(auto(q, k, v, True)),
        np.asarray(g.full_attention(q, k, v, True)),
        rtol=2e-4, atol=2e-5,
    )


# ---------------------------------------------------------------------------
# The causal schedule inside a grid step (PR 28)
# ---------------------------------------------------------------------------

_SCHEDULE_T = (64, 128, 192, 256, 512, 1024, 1536, 2048, 4096, 8192)


@pytest.mark.parametrize("own_is_q", [True, False], ids=["fwd-dq", "dkv"])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("t", _SCHEDULE_T)
def test_causal_schedule_loses_no_pair(t, d, own_is_q):
    """Every causal pair lies in exactly one scored sub-tile, every sub-tile
    that holds a masked pair is one the kernel masks, none is scored that
    holds no causal pair, and ``scheduled_pairs`` is the brute-force count
    of the same walk.  From T = 1,024 at most half again the causal pairs
    are scored (one [512, 1024] tile scored 1.998 times them)."""
    blocks = _blocks_for(t, d)
    assert t % blocks.tile == 0 and blocks.tile % blocks.sub == 0
    hits = np.zeros((t, t), np.uint8)
    for r0, nr, c0, nc, masked in scheduled_sub_tiles(t, d, True, own_is_q):
        hits[r0:r0 + nr, c0:c0 + nc] += 1
        assert r0 + nr - 1 >= c0, "a sub-tile with no causal pair is scored"
        assert masked == (r0 < c0 + nc - 1), (r0, nr, c0, nc, masked)
    assert hits.max() == 1
    assert np.tril(hits).sum() == t * (t + 1) // 2, "a causal pair is lost"
    assert scheduled_pairs(t, d, True) == int(hits.sum())
    if t >= 1024:
        assert scheduled_pairs(t, d, True) / (t * (t + 1) / 2) <= 1.5


@pytest.mark.parametrize("t", [64, 1024, 2048])
def test_noncausal_schedule_is_the_full_range(t):
    tiles = list(scheduled_sub_tiles(t, 64, False))
    assert not any(masked for *_, masked in tiles)
    assert scheduled_pairs(t, 64, False) == t * t


def _fwd_and_grads(fn, q, k, v, causal):
    t = q.shape[-2]
    w = (jnp.arange(t, dtype=jnp.float32)[:, None] / t + 0.25)

    def loss(q, k, v):
        return jnp.sum(fn(q, k, v, causal).astype(jnp.float32) * w)

    return fn(q, k, v, causal), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


# (T, d): the benchmark cell's shape class (one [1024, 1024] tile, walked in
# sixteen sub-tiles); a tile of four sub-tiles; several tiles, so that the
# carried accumulators and the tile-level skip run; a tile that is one
# sub-tile.
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("t,d", [(1024, 64), (512, 32), (2048, 64),
                                 (192, 16)])
def test_flash_schedule_matches_full(t, d, causal, dtype):
    if t == 512:
        assert _blocks_for(t, d) == (512, 256)
    ks = jax.random.split(jax.random.PRNGKey(t + d), 3)
    q, k, v = (jax.random.normal(kk, (1, 2, t, d), jnp.float32).astype(dtype)
               for kk in ks)
    ref_o, ref_g = _fwd_and_grads(full_attention, q, k, v, causal)
    got_o, got_g = jax.jit(_fwd_and_grads, static_argnums=(0, 4))(
        flash_attention, q, k, v, causal)
    tol = dict(rtol=5e-4, atol=5e-5) if dtype == jnp.float32 \
        else dict(rtol=3e-2, atol=3e-2)
    for got, ref in zip((got_o, *got_g), (ref_o, *ref_g)):
        assert got.dtype == dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32), **tol)


def _chunk_reference(q, k, v, causal):
    """(o, lse) of one chunk, plainly."""
    t, d = q.shape[-2:]
    s = jnp.einsum("bqd,bkd->bqk", q, k) / np.sqrt(d)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", jnp.exp(s - lse[..., None]), v), lse


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("t", [512, 1024])
def test_flash_chunk_lse_cotangent(t, causal):
    """``flash_chunk`` through the sub-tile schedule, with a cotangent on
    the logsumexp output (ring attention's merge differentiates it)."""
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q, k, v = (jax.random.normal(kk, (2, t, 32), jnp.float32)
               for kk in ks[:3])
    w_lse = jax.random.normal(ks[3], (2, t), jnp.float32)

    def loss(fn):
        def f(q, k, v):
            o, lse = fn(q, k, v, causal)
            return jnp.sum(o * o) + jnp.sum(lse * w_lse)
        return f

    ref = jax.grad(loss(_chunk_reference), argnums=(0, 1, 2))(q, k, v)
    got = jax.jit(jax.grad(loss(flash_chunk), argnums=(0, 1, 2)))(q, k, v)
    for g, r in zip(got, ref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=5e-4, atol=5e-5)
    o, lse = flash_chunk(q, k, v, causal)
    ro, rlse = _chunk_reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ro),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(rlse),
                               rtol=2e-4, atol=2e-5)
