"""Native C++ data-loader tier: the library must build in-image, and every
routine must match its Python fallback bit-for-bit (the determinism contract
in trustworthy_dl_tpu/native/__init__.py)."""

import numpy as np
import pytest

from trustworthy_dl_tpu import native
from trustworthy_dl_tpu.data.loader import (
    ArrayDataLoader,
    PrefetchLoader,
    get_dataloader,
)


@pytest.fixture(scope="module")
def lib_built():
    path = native.build_library()
    if path is None:
        pytest.skip("no C++ toolchain in this environment")
    assert native.native_available()
    return path


def _python_fallback(fn, *args, **kwargs):
    """Run a native-module function with the library forcibly absent."""
    saved_lib, saved_tried = native._LIB, native._LIB_TRIED
    native._LIB, native._LIB_TRIED = None, True
    try:
        return fn(*args, **kwargs)
    finally:
        native._LIB, native._LIB_TRIED = saved_lib, saved_tried


def test_splitmix_stream_cpp_matches_python(lib_built):
    got = native.splitmix_fill(12345, 4096)
    ref = _python_fallback(native.splitmix_fill, 12345, 4096)
    np.testing.assert_array_equal(got, ref)


def test_synthetic_tokens_cpp_matches_python(lib_built):
    got = native.synthetic_tokens(10_000, 512, 7)
    ref = _python_fallback(native.synthetic_tokens, 10_000, 512, 7)
    np.testing.assert_array_equal(got, ref)
    # Learnability contract: mostly the affine chain, ~10% resets.
    a, b, v = 31, 7, 512
    follows = np.mean(got[1:] == (a * got[:-1].astype(np.int64) + b) % v)
    assert 0.85 < follows < 0.95


def test_permutation_cpp_matches_python(lib_built):
    got = native.permutation(99, 1000)
    ref = _python_fallback(native.permutation, 99, 1000)
    np.testing.assert_array_equal(got, ref)
    assert sorted(got.tolist()) == list(range(1000))


def test_gather_rows_cpp_matches_numpy(lib_built):
    src = np.random.default_rng(0).normal(size=(500, 17, 3)).astype(np.float32)
    idx = native.permutation(1, 500)[:128]
    got = native.gather_rows(src, idx)
    np.testing.assert_array_equal(got, src[idx])
    # int rows too (token batches)
    toks = np.arange(4000, dtype=np.int32).reshape(400, 10)
    idx2 = native.permutation(2, 400)[:64]
    got2 = native.gather_rows(toks, idx2)
    np.testing.assert_array_equal(got2, toks[idx2])


def test_dataloader_batches_identical_native_vs_fallback(lib_built):
    x = np.arange(320, dtype=np.int32).reshape(40, 8)
    y = x + 1
    native_batches = list(ArrayDataLoader(x, y, batch_size=8, seed=3))
    fallback_batches = _python_fallback(
        lambda: list(ArrayDataLoader(x, y, batch_size=8, seed=3))
    )
    assert len(native_batches) == len(fallback_batches) == 5
    for a, b in zip(native_batches, fallback_batches):
        np.testing.assert_array_equal(a["input"], b["input"])
        np.testing.assert_array_equal(a["target"], b["target"])


def test_prefetch_loader_preserves_stream():
    dl = get_dataloader("openwebtext", batch_size=4, seq_len=16,
                        vocab_size=128, num_examples=32)
    direct = [b["input"].copy() for b in dl]
    dl2 = get_dataloader("openwebtext", batch_size=4, seq_len=16,
                         vocab_size=128, num_examples=32)
    prefetched = [b["input"].copy() for b in PrefetchLoader(dl2, depth=2)]
    assert len(direct) == len(prefetched) > 0
    for a, b in zip(direct, prefetched):
        np.testing.assert_array_equal(a, b)


def test_prefetch_loader_propagates_errors():
    def boom():
        yield {"input": np.zeros(1), "target": np.zeros(1)}
        raise RuntimeError("producer died")

    loader = PrefetchLoader(boom(), depth=1)
    with pytest.raises(RuntimeError, match="producer died"):
        list(loader)


def test_window_gather_native_matches_fallback():
    """The C++ random-window sampler and the numpy fallback must produce
    bit-identical batches (same splitmix offsets)."""
    import os

    from trustworthy_dl_tpu import native

    if not native.native_available():
        pytest.skip("native library unavailable")
    stream = np.arange(10_000, dtype=np.int32) % 997
    a_in, a_tg = native.window_gather(stream, seq_len=32, batch=128, seed=42)
    # Force the fallback path via the internal implementation.
    offs = (native.splitmix_fill(42, 128) % np.uint64(10_000 - 32)).astype(
        np.int64
    )
    gather = offs[:, None] + np.arange(33, dtype=np.int64)[None, :]
    windows = stream[gather]
    np.testing.assert_array_equal(a_in, windows[:, :-1])
    np.testing.assert_array_equal(a_tg, windows[:, 1:])
    # targets are the shifted inputs
    np.testing.assert_array_equal(a_in[:, 1:], a_tg[:, :-1])


def test_token_stream_loader_contract():
    """TokenStreamLoader: deterministic per epoch, fresh windows per batch,
    {'input','target'} contract, trains with the engine loaders."""
    from trustworthy_dl_tpu.data import TokenStreamLoader, get_dataloader

    stream = np.arange(5_000, dtype=np.int32) % 101
    dl = TokenStreamLoader(stream, batch_size=8, seq_len=16,
                           steps_per_epoch=3, seed=7)
    assert len(dl) == 3
    e0 = [b for b in dl]
    e1 = [b for b in dl]
    assert len(e0) == 3
    assert e0[0]["input"].shape == (8, 16)
    np.testing.assert_array_equal(e0[0]["input"][:, 1:],
                                  e0[0]["target"][:, :-1])
    # different batches and different epochs draw different windows
    assert not np.array_equal(e0[0]["input"], e0[1]["input"])
    assert not np.array_equal(e0[0]["input"], e1[0]["input"])
    # same (seed, epoch, step) reproduces exactly
    dl2 = TokenStreamLoader(stream, batch_size=8, seq_len=16,
                            steps_per_epoch=3, seed=7)
    np.testing.assert_array_equal(next(iter(dl2))["input"], e0[0]["input"])

    wdl = get_dataloader("openwebtext", batch_size=4, seq_len=16,
                         vocab_size=128, num_examples=32,
                         sampling="windows")
    batch = next(iter(wdl))
    assert batch["input"].shape == (4, 16)


def test_token_stream_loader_no_epoch_step_collision():
    """(epoch, step) folds through splitmix: long epochs must never repeat
    a batch across epoch boundaries (a linear mix collided at step 10007)."""
    from trustworthy_dl_tpu.data import TokenStreamLoader

    stream = np.arange(4_000, dtype=np.int32)
    dl = TokenStreamLoader(stream, batch_size=2, seq_len=8,
                           steps_per_epoch=10_008, seed=0)
    it0 = iter(dl)
    first_epoch = [next(it0)["input"] for _ in range(10_008)]
    it1 = iter(dl)
    b1_0 = next(it1)["input"]
    assert not any(np.array_equal(b1_0, b) for b in first_epoch[10_000:])
    assert not np.array_equal(b1_0, first_epoch[0])


def test_text_file_byte_tier(tmp_path, monkeypatch):
    """A plain .txt under $TDDL_DATA_DIR trains byte-level: ids are the
    file's UTF-8 bytes with a 95/5 train/validation split."""
    from trustworthy_dl_tpu.data import get_dataloader

    text = ("the quick brown fox jumps over the lazy dog. " * 200).encode()
    (tmp_path / "openwebtext.txt").write_bytes(text)
    monkeypatch.setenv("TDDL_DATA_DIR", str(tmp_path))
    dl = get_dataloader("openwebtext", batch_size=4, seq_len=32,
                        num_examples=16)
    batch = next(iter(dl))
    assert batch["input"].shape == (4, 32)
    assert batch["input"].max() < 256 and batch["input"].min() >= 0
    np.testing.assert_array_equal(batch["input"][:, 1:],
                                  batch["target"][:, :-1])
    # windows sampling rides the same stream
    wdl = get_dataloader("openwebtext", batch_size=4, seq_len=32,
                         num_examples=16, sampling="windows")
    assert next(iter(wdl))["input"].shape == (4, 32)


def test_library_is_keyed_on_the_source_hash(lib_built, tmp_path,
                                             monkeypatch):
    """The built library is named after a hash of dataloader.cpp — not
    trusted by mtime: a copied tree has arbitrary mtimes, and a library
    built from another source must never be loaded for this one."""
    import hashlib
    import os

    with open(native._source_path(), "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    assert os.path.basename(lib_built) == f"libtddl_native.{digest}.so"
    # An older library lying beside the source is not what gets loaded,
    # however new its mtime.
    stale = os.path.join(os.path.dirname(lib_built), "libtddl_native.so")
    assert lib_built != stale
    # A changed source names a different library.
    changed = tmp_path / "dataloader.cpp"
    changed.write_bytes(open(native._source_path(), "rb").read() + b"\n//x\n")
    monkeypatch.setattr(native, "_source_path", lambda: str(changed))
    assert os.path.basename(native._lib_path()) != os.path.basename(lib_built)
