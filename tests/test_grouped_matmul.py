"""The grouped-products kernel (``ops/grouped_matmul.py``) in Pallas
interpret mode against ``jax.lax.ragged_dot``, its tile rule, its counter
and its dispatch; ``held_experts`` through it against ``held_experts``
through ``ragged_dot``.  That it lowers for the chip is
``tests/test_chip_compile.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trustworthy_dl_tpu import ops
from trustworthy_dl_tpu.models import moe
from trustworthy_dl_tpu.ops import grouped_matmul as gm

TOL = 1e-5          # of the largest product: float32 sums in another order


def _balanced(live, groups):
    return [live // groups + (g < live % groups) for g in range(groups)]


def _skewed(live, groups, seed):
    """Sizes a router with a bias sends: the fullest some 2.5 times the
    mean."""
    rng = np.random.default_rng(seed)
    return rng.multinomial(live, rng.dirichlet(np.full(groups, 4.0)))


# (m, k, n, group sizes, (tm, tk, tn) or None = the rule through the
# dispatch, dtype)
CASES = {
    "a-group-with-no-row": (64, 128, 256, [5, 0, 9, 0, 3], (16, 128, 128),
                            jnp.bfloat16),
    "a-group-straddles-two-tiles": (64, 128, 128, [10, 12, 4],
                                    (16, 128, 128), jnp.bfloat16),
    "a-group-straddles-three-tiles": (64, 128, 128, [10, 30, 3],
                                      (16, 128, 128), jnp.bfloat16),
    "every-row-live": (64, 128, 128, [16, 20, 28], (16, 128, 128),
                       jnp.bfloat16),
    "no-row-live": (64, 128, 128, [0, 0, 0, 0], (16, 128, 128),
                    jnp.bfloat16),
    "live-rows-end-inside-a-tile": (64, 128, 128, [7, 17, 13],
                                    (16, 128, 128), jnp.bfloat16),
    "fullest-group-3-times-the-mean": (256, 128, 128,
                                       [36, 4, 12, 8, 10, 6, 12, 8],
                                       (32, 128, 128), jnp.bfloat16),
    "k-in-two-tiles": (64, 256, 256, [3, 0, 20, 5, 0, 17], (16, 128, 128),
                       jnp.bfloat16),
    "rows-off-the-tile-float32": (50, 32, 48, [1, 20, 1, 1, 3], None,
                                  jnp.float32),
    "the-rule-at-a-decode-call": (512, 128, 256, _balanced(64, 40), None,
                                  jnp.bfloat16),
    "the-rule-at-a-chunk-call": (8192, 128, 256, _skewed(1024, 40, 5), None,
                                 jnp.bfloat16),
}


@pytest.mark.parametrize("case", CASES, ids=list(CASES))
def test_kernel_is_ragged_dot_on_the_live_rows(monkeypatch, case):
    m, k, n, sizes, tiles, dtype = CASES[case]
    sizes = jnp.asarray(sizes, jnp.int32)
    live = int(jnp.sum(sizes))
    key = jax.random.PRNGKey(len(case))
    rows = jax.random.normal(key, (m, k), jnp.float32)
    # Garbage behind the last group: it may not reach a live row.
    rows = jnp.where(jnp.arange(m)[:, None] < live, rows, jnp.nan)
    rows = rows.astype(dtype)
    w = (jax.random.normal(jax.random.fold_in(key, 1),
                           (sizes.shape[0], k, n)) * 0.1).astype(dtype)
    want = jax.lax.ragged_dot(rows, w, sizes,
                              preferred_element_type=jnp.float32)
    if tiles is None:
        monkeypatch.setenv("TDDL_GROUPED_MATMUL", "1")
        tm = gm.tiling(m, k, n, sizes.shape[0], jnp.dtype(dtype).itemsize)[0]
        assert tm == {512: 16, 8192: 128, 50: 16}[m]
        got = gm.grouped_matmul(rows, w, sizes)
    else:
        got = gm._gmm_call(rows, w, sizes, tiles=tiles, interpret=True)
    assert got.shape == (m, n) and got.dtype == jnp.float32
    if live:
        scale = float(jnp.max(jnp.abs(want[:live])))
        assert float(jnp.max(jnp.abs(got[:live] - want[:live]))) \
            <= TOL * scale
        assert bool(jnp.all(jnp.isfinite(got[:live])))


def test_row_tile_follows_the_rows_a_group_gets():
    assert gm.row_tile(512, 40) == 16        # a decode call: 12.8 a group
    assert gm.row_tile(8192, 40) == 128      # a chunk call: 204.8
    assert [gm.row_tile(m, 8) for m in (1, 128, 129, 256, 257, 512, 1024,
                                        1025, 1 << 20)] == [
        16, 16, 32, 32, 64, 64, 128, 128, 128]


def test_tiling_fills_half_of_vmem_with_the_weights_tile():
    """K whole, then the widest N: at the cell's widths the double-buffered
    bfloat16 weights tiles are 8 MiB and 5 MiB of the 8 MiB budget."""
    assert gm.tiling(512, 4096, 2560, 40, 2) == (16, 4096, 512)
    assert gm.tiling(8192, 1280, 4096, 40, 2) == (128, 1280, 1024)
    assert gm.tiling(512, 32768, 256, 40, 2) == (16, 16384, 128)
    assert gm.tiling(64, 32, 48, 4, 4) == (16, 32, 48)   # off the lanes: whole
    assert gm.tiling(64, 3000, 3000, 4, 4) is None       # ... and too large


def test_scheduled_rows_and_the_dispatch_take_one_rule(monkeypatch):
    sizes = [10, 12, 4]
    # Tiles of 16: tile 0 holds groups 0 and 1, tile 1 groups 1 and 2.
    assert gm.scheduled_rows(32, sizes) == 4 * 16
    assert gm.scheduled_rows(32, [0, 0, 0]) == 0
    assert gm.scheduled_rows(32, [16, 0, 16]) == 32      # no padded row
    seen = []
    real = gm._gmm_call

    def spy(rows, w, group_sizes, tiles, interpret):
        seen.append(tiles)
        return real(rows, w, group_sizes, tiles=tiles, interpret=interpret)

    monkeypatch.setattr(gm, "_gmm_call", spy)
    monkeypatch.setenv("TDDL_GROUPED_MATMUL", "1")
    args = (jnp.ones((32, 128), jnp.bfloat16),
            jnp.ones((3, 128, 128), jnp.bfloat16),
            jnp.asarray(sizes, jnp.int32))
    gm.grouped_matmul(*args)
    assert seen[-1][0] == 16
    # Another rule moves both: in tiles of 32 one tile holds all three.
    monkeypatch.setattr(gm, "row_tile", lambda m, groups: 32)
    assert gm.scheduled_rows(32, sizes) == 3 * 32
    gm.grouped_matmul(*args)
    assert seen[-1][0] == 32


def test_off_the_chip_and_under_partitioning_ragged_dot_runs(monkeypatch):
    def refuse(*args, **kwargs):
        raise AssertionError("the kernel was dispatched")

    monkeypatch.setattr(gm, "_gmm_call", refuse)
    monkeypatch.delenv("TDDL_GROUPED_MATMUL", raising=False)
    rows = jnp.ones((64, 128), jnp.bfloat16)
    w = jnp.ones((3, 128, 128), jnp.bfloat16)
    sizes = jnp.asarray([10, 12, 4], jnp.int32)
    want = jax.lax.ragged_dot(rows, w, sizes,
                              preferred_element_type=jnp.float32)
    assert jnp.array_equal(gm.grouped_matmul(rows, w, sizes)[:26], want[:26])
    # On the TPU backend, a program GSPMD will partition takes it too ...
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    class FourChips:
        size = 4

    ops.for_mesh(gm.grouped_matmul, FourChips)(rows, w, sizes)
    # ... and one device takes the kernel.
    with pytest.raises(AssertionError, match="dispatched"):
        gm.grouped_matmul(rows, w, sizes)


def test_held_experts_through_the_kernel_is_held_experts_through_ragged_dot(
        monkeypatch):
    """The decoder test's layer (16 experts of 32 x 16, top 2), a share of
    4 experts, some tokens padding."""
    rng = np.random.default_rng(1)
    draw = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
    router, bias = draw(32, 16), draw(16) * 0.1
    w_gate_up, w_down = draw(16, 32, 32), draw(16, 16, 32)
    x = draw(40, 32)
    valid = jnp.arange(40) < 33
    chosen, weights = moe.route_top_k(x, router, bias, 2)

    def share():
        return moe.held_experts(x, chosen, weights, w_gate_up[6:10],
                                w_down[6:10], 6, valid)

    monkeypatch.delenv("TDDL_GROUPED_MATMUL", raising=False)
    want, want_pairs = share()
    calls = []
    real = gm._gmm_call
    monkeypatch.setattr(gm, "_gmm_call", lambda *a, **kw: (
        calls.append(kw["tiles"]), real(*a, **kw))[1])
    monkeypatch.setenv("TDDL_GROUPED_MATMUL", "1")
    got, got_pairs = share()
    assert calls == [(32, 32, 32), (32, 16, 32)]      # both products
    assert jnp.array_equal(got_pairs, want_pairs) and int(
        jnp.sum(want_pairs)) > 8
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-6 * float(
        jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got[33:]))) == 0.0
