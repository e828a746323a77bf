"""Each operation and byte count against a hand count at one shape, the
roofline arithmetic and the peak table."""

import pytest

from bench_paths import ROOT  # noqa: F401

from benchmark.harness import kernel_work as kw
from benchmark.harness import peaks
from benchmark.harness.families import gpt2

V5E = peaks.peak("TPU v5 lite")


def test_peak_table():
    assert V5E.flops_bf16 == 197e12 and V5E.hbm_bytes_per_s == 819e9
    assert V5E.hbm_bytes == 16e9 and "v5e" in V5E.source
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peak("cpu")


@pytest.mark.parametrize("t_q,t_k,want", [(4, 4, 10), (1, 9, 9), (2, 6, 11),
                                          (1024, 1024, 524800)])
def test_causal_pairs(t_q, t_k, want):
    assert kw.causal_pairs(t_q, t_k) == want


def test_flash_forward_by_hand():
    # 1 row, 1 head, T=4, D=8: 10 causal pairs, two products of 2*D each.
    work = kw.flash_fwd(1, 1, 4, 8)
    assert work.flops == 2 * (2 * 10 * 8) == 320
    assert work.bytes == 4 * 4 * 8 * 2 + 4 * 4
    big = kw.flash_fwd(8, 12, 1024, 64)
    assert big.flops == 8 * 12 * 4 * 524800 * 64


def test_flash_backward_is_five_products():
    fwd, bwd = kw.flash_fwd(2, 3, 128, 64), kw.flash_bwd(2, 3, 128, 64)
    assert bwd.flops == pytest.approx(2.5 * fwd.flops)
    assert kw.flash_bwd(1, 1, 4, 8).bytes == 8 * 4 * 8 * 2 + 2 * 4 * 4


@pytest.mark.parametrize("sizes,want", [
    (dict(vocab_size=50257, n_positions=1024, n_layer=12, n_embd=768),
     124_439_808),
    (dict(vocab_size=50257, n_positions=1024, n_layer=36, n_embd=1280),
     774_030_080)])
def test_gpt2_parameter_counts(sizes, want):
    assert gpt2.param_count(sizes) == want
    assert kw.train_flops_per_token(want) == 6.0 * want


def test_roofline_says_which_bound_and_cannot_flatter():
    compute = kw.Work(197e12, 1.0)
    pct, bound = kw.roofline_pct(compute, 2.0, V5E)
    assert bound == "compute" and pct == pytest.approx(50.0)
    memory = kw.Work(1.0, 819e9)
    pct, bound = kw.roofline_pct(memory, 4.0, V5E)
    assert bound == "memory" and pct == pytest.approx(25.0)
