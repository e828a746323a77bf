"""Data loading — the implied ``utils.data_loader.get_dataloader``
(imported at experiment_runner.py:24; call shape at :100-110 and
distributed_trainer.py:395-398: iterables of ``{'input','target'}`` dict
batches).

This environment is zero-egress, so each dataset has two tiers:

* real data if present under ``$TDDL_DATA_DIR`` —
  ``openwebtext.bin`` (a flat uint16/uint32 token memmap, nanoGPT layout) or
  ``cifar10/`` (numpy ``.npz`` with x_train/y_train/x_test/y_test);
* otherwise a deterministic *learnable* synthetic source — an affine
  next-token process for LM data, class-conditional Gaussian images for
  CIFAR — so integration tests can assert that loss actually decreases
  (replacing the reference's fabricated loss curves,
  experiment_runner.py:201-216).
"""

from __future__ import annotations

import os
import queue
import threading
from typing import Any, Dict, Iterator, Optional

import numpy as np

from trustworthy_dl_tpu import native
from trustworthy_dl_tpu.utils.profiling import span


class ArrayDataLoader:
    """Deterministic batched iterator over {'input','target'} arrays.

    Epoch shuffles and per-batch row gathers run on the native C++ tier
    (trustworthy_dl_tpu/native) when the library is available, with bit-exact
    Python fallbacks — batch contents are identical either way."""

    def __init__(self, inputs: np.ndarray, targets: np.ndarray,
                 batch_size: int, shuffle: bool = True, seed: int = 0,
                 drop_last: bool = True):
        assert len(inputs) == len(targets)
        self.inputs = np.ascontiguousarray(inputs)
        self.targets = np.ascontiguousarray(targets)
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.drop_last = drop_last
        self._epoch = 0

    def __len__(self) -> int:
        n = len(self.inputs) // self.batch_size
        if not self.drop_last and len(self.inputs) % self.batch_size:
            n += 1
        return n

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        if self.shuffle:
            idx = native.permutation(self.seed + self._epoch, len(self.inputs))
        else:
            idx = np.arange(len(self.inputs), dtype=np.int64)
        self._epoch += 1
        # ``batch_size`` is re-read every batch so a live re-size (elastic
        # topology change mid-epoch, trainer._resize_loader) takes effect
        # on the next batch, not the next epoch.
        start = 0
        while start < len(idx):
            bs = self.batch_size
            sel = idx[start:start + bs]
            start += bs
            if len(sel) == 0 or (self.drop_last and len(sel) < bs):
                break
            yield {
                "input": native.gather_rows(self.inputs, sel),
                "target": native.gather_rows(self.targets, sel),
            }


class TokenStreamLoader:
    """Random-window batches over a contiguous token stream — the
    nanoGPT-style LM sampler: every batch draws ``batch_size`` windows of
    ``seq_len + 1`` tokens at fresh splitmix-derived offsets (native
    multi-threaded gather, bit-exact fallback), so an "epoch" is a step
    budget rather than a fixed partition of the stream.

    Deterministic: batch k of epoch e depends only on (seed, e, k).
    ``freeze_epoch=True`` pins every iteration to epoch 0 — a validation
    loader must yield the SAME windows on every call, otherwise val loss is
    computed on a fresh sample each epoch and any abandoned ``iter()``
    silently shifts subsequent data."""

    def __init__(self, stream: np.ndarray, batch_size: int, seq_len: int,
                 steps_per_epoch: int, seed: int = 0,
                 freeze_epoch: bool = False):
        self.stream = np.ascontiguousarray(stream, np.int32)
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.steps_per_epoch = steps_per_epoch
        self.seed = seed
        self.freeze_epoch = freeze_epoch
        self._epoch = 0

    def __len__(self) -> int:
        return self.steps_per_epoch

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        epoch = 0 if self.freeze_epoch else self._epoch
        if not self.freeze_epoch:
            self._epoch += 1
        mask = (1 << 64) - 1
        # Two splitmix rounds fold (seed, epoch, step) into the batch seed:
        # a linear small-prime mix would collide across (epoch, step)
        # pairs (e.g. epoch e step P == epoch e+1 step 0) and silently
        # repeat batches on long epochs.
        k_epoch = int(native.splitmix_fill(
            ((self.seed & ((1 << 32) - 1)) << 32 | (epoch & ((1 << 32) - 1))),
            1,
        )[0])
        for step in range(self.steps_per_epoch):
            seed = int(native.splitmix_fill((k_epoch + step) & mask, 1)[0])
            inputs, targets = native.window_gather(
                self.stream, self.seq_len, self.batch_size, seed
            )
            yield {"input": inputs, "target": targets}


class PrefetchLoader:
    """Background-thread prefetch over any batch iterable: batch k+1
    assembles on the host (native gathers) while batch k trains on device —
    double buffering for the input pipeline (depth configurable).  The
    consumer's blocking wait for a batch is the span ``train.data_wait``
    (recorded into ``timer``, a ``StepTimeReporter``, when one is set)."""

    def __init__(self, loader: Any, depth: int = 2, timer: Any = None):
        self.loader = loader
        self.depth = max(1, depth)
        self.timer = timer

    def __len__(self) -> int:
        return len(self.loader)

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        stop = threading.Event()
        sentinel = object()
        errbox: list = []

        def produce() -> None:
            try:
                for batch in self.loader:
                    # Bounded put that notices consumer cancellation — a
                    # plain q.put would block forever if the consumer
                    # abandoned iteration with the queue full.
                    while not stop.is_set():
                        try:
                            q.put(batch, timeout=0.05)
                            break
                        except queue.Full:
                            continue
                    if stop.is_set():
                        return
            except BaseException as exc:  # surface in the consumer
                errbox.append(exc)
            finally:
                # The sentinel needs the same cancellation-aware bounded put
                # as batches: with the queue still holding undelivered
                # batches a put_nowait would drop the sentinel and leave a
                # live consumer blocked on q.get() forever.
                while not stop.is_set():
                    try:
                        q.put(sentinel, timeout=0.05)
                        break
                    except queue.Full:
                        continue

        worker = threading.Thread(target=produce, daemon=True)
        worker.start()
        try:
            while True:
                with span("train.data_wait", self.timer):
                    item = q.get()
                if item is sentinel:
                    break
                yield item
        finally:
            # Runs on normal exhaustion AND on early exit (break / GC of the
            # generator): release the producer and reap the thread.
            stop.set()
            while True:
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
            worker.join(timeout=5)
        if errbox:
            raise errbox[0]


# ---------------------------------------------------------------------------
# Synthetic sources (deterministic, learnable)
# ---------------------------------------------------------------------------


def _synthetic_tokens(num_tokens: int, vocab_size: int, seed: int) -> np.ndarray:
    """Affine next-token process with 10% uniform noise: t_{i+1} =
    (a*t_i + b) mod V usually — low-entropy enough that a model visibly
    learns, noisy enough that loss stays finite and non-zero.  Generated by
    the native tier (C++ when available, bit-exact numpy otherwise)."""
    return native.synthetic_tokens(num_tokens, vocab_size, seed)


def _synthetic_images(num: int, num_classes: int, shape, seed: int):
    """Class-conditional Gaussian images: per-class fixed mean pattern +
    noise.  Linearly separable → any conv net's loss drops fast."""
    rng = np.random.default_rng(seed)
    h, w, c = shape
    prototypes = rng.normal(0, 1, size=(num_classes, h, w, c)).astype(np.float32)
    labels = rng.integers(0, num_classes, num).astype(np.int32)
    images = prototypes[labels] + rng.normal(0, 0.7, size=(num, h, w, c)).astype(
        np.float32
    )
    return images, labels


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def get_dataloader(
    dataset_name: str,
    split: str = "train",
    batch_size: int = 32,
    seq_len: int = 128,
    vocab_size: int = 50257,
    num_examples: Optional[int] = None,
    seed: int = 0,
    data_dir: Optional[str] = None,
    sampling: str = "epoch",
    image_size: Optional[int] = None,
) -> Any:
    """Reference signature (experiment_runner.py:100-110) with TPU-side
    extensions (seq_len/vocab_size for LM synthesis; ``sampling``:
    "epoch" partitions the stream into fixed shuffled windows,
    "windows" draws fresh random windows every batch — the nanoGPT-style
    sampler via the native gather, better coverage on real corpora;
    ``image_size``: side length for the SYNTHETIC vision tier — conv
    models pool globally, so scenario tests can run on smaller frames
    at a fraction of the compute; ignored for real .npz data)."""
    name = dataset_name.lower()
    if sampling not in ("epoch", "windows"):
        raise ValueError(
            f"sampling must be 'epoch' or 'windows', got {sampling!r}"
        )
    data_dir = data_dir or os.environ.get("TDDL_DATA_DIR", "")
    split_seed = seed + (0 if split == "train" else 10_000)

    if name in ("openwebtext", "wikitext", "lm", "synthetic_lm"):
        n = num_examples or (2048 if split == "train" else 256)
        bin_path = os.path.join(data_dir, f"{name}.bin") if data_dir else ""
        txt_path = os.path.join(data_dir, f"{name}.txt") if data_dir else ""
        if bin_path and os.path.exists(bin_path):
            tokens = np.memmap(bin_path, dtype=np.uint16, mode="r")
            # Hold out the final 5% for validation.
            cut = int(len(tokens) * 0.95)
            tokens = tokens[:cut] if split == "train" else tokens[cut:]
            tokens = np.asarray(tokens, np.int32)
        elif txt_path and os.path.exists(txt_path):
            # Byte-level tier: any plain-text corpus trains without a
            # tokenizer — ids are raw UTF-8 bytes (256 ≤ every GPT vocab).
            if vocab_size < 256:
                raise ValueError(
                    f"byte-level corpus {txt_path} needs vocab_size >= 256 "
                    f"(got {vocab_size}): byte ids would exceed the "
                    "embedding table"
                )
            raw = np.memmap(txt_path, dtype=np.uint8, mode="r")
            cut = int(len(raw) * 0.95)
            tokens = np.asarray(raw[:cut] if split == "train" else raw[cut:],
                                np.int32)
        else:
            tokens = _synthetic_tokens(n * (seq_len + 1) + 1,
                                       min(vocab_size, 512), split_seed)
        if sampling == "windows":
            steps = max(n // max(batch_size, 1), 1)
            return TokenStreamLoader(tokens, batch_size, seq_len,
                                     steps_per_epoch=steps, seed=split_seed,
                                     freeze_epoch=(split != "train"))
        usable = (len(tokens) - 1) // seq_len
        usable = min(usable, n)
        window = tokens[: usable * seq_len + 1]
        inputs = np.stack([window[i * seq_len:(i + 1) * seq_len]
                           for i in range(usable)])
        targets = np.stack([window[i * seq_len + 1:(i + 1) * seq_len + 1]
                            for i in range(usable)])
        return ArrayDataLoader(inputs, targets, batch_size, shuffle=True,
                               seed=split_seed)

    if name in ("cifar10", "cifar-10", "cifar100", "imagenet", "synthetic_vision"):
        if sampling == "windows":
            raise ValueError(
                "sampling='windows' is a token-stream sampler; vision "
                "datasets use epoch sampling"
            )
        num_classes = 100 if "100" in name else (1000 if "imagenet" in name else 10)
        side = image_size or (224 if "imagenet" in name else 32)
        shape = (side, side, 3)
        n = num_examples or (2048 if split == "train" else 512)
        npz_path = os.path.join(data_dir, "cifar10", "cifar10.npz") if data_dir else ""
        if name.startswith("cifar10") and npz_path and os.path.exists(npz_path):
            blob = np.load(npz_path)
            if split == "train":
                images, labels = blob["x_train"], blob["y_train"]
            else:
                images, labels = blob["x_test"], blob["y_test"]
            images = (images.astype(np.float32) / 127.5) - 1.0
            labels = labels.reshape(-1).astype(np.int32)
        else:
            images, labels = _synthetic_images(n, num_classes, shape, split_seed)
        return ArrayDataLoader(images, labels, batch_size, shuffle=True,
                               seed=split_seed)

    raise ValueError(f"unknown dataset {dataset_name!r}")
