"""Datasets (reference component C4).

The reference uses torchvision CIFAR10 (auto-download, Normalize with CIFAR
stats — reference 1.dataparallel.py:124-129), MNIST with per-rank data dirs
(reference 5.2.horovod_pytorch_mnist.py:134-155) and ImageFolder for ImageNet
(reference 6.distributed_slurm_main.py:130-159).

TPU-first redesign:

* datasets are in-memory uint8 numpy arrays on the host; normalization and
  train-time augmentation (random crop + flip) happen **on device inside the
  jitted step** — the idiomatic replacement for the reference's buggy
  CUDA-stream GPU prefetcher that normalized on a side stream
  (reference 4.apex_distributed.py:80-133, disabled in 4b:80);
* real CIFAR-10 (cifar-10-batches-py pickles) and MNIST (idx files) are loaded
  if present under ``--data``; otherwise a deterministic *synthetic* set with
  class-conditional structure is generated, because this environment has no
  network egress (torchvision's auto-download cannot work). Synthetic data is
  learnable, so convergence tests remain meaningful.
"""

from __future__ import annotations

import gzip
import os
import pickle
import struct
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

# CIFAR10 channel stats, as hard-coded by the reference
# (reference 1.dataparallel.py:127-129: mean=[0.4914,0.4822,0.4465], std=[0.2023,0.1994,0.2010])
CIFAR10_MEAN = np.array([0.4914, 0.4822, 0.4465], np.float32)
CIFAR10_STD = np.array([0.2023, 0.1994, 0.2010], np.float32)
# MNIST stats (reference 5.2.horovod_pytorch_mnist.py:140: Normalize((0.1307,), (0.3081,)))
MNIST_MEAN = np.array([0.1307], np.float32)
MNIST_STD = np.array([0.3081], np.float32)
IMAGENET_MEAN = np.array([0.485, 0.456, 0.406], np.float32)  # reference 6...py:133
IMAGENET_STD = np.array([0.229, 0.224, 0.225], np.float32)


@dataclass
class ArrayDataset:
    """Host-side dataset: uint8 images (N,H,W,C) + int32 labels (N,)."""

    images: np.ndarray
    labels: np.ndarray
    mean: np.ndarray
    std: np.ndarray
    num_classes: int
    name: str = "dataset"

    def __len__(self) -> int:
        return self.images.shape[0]

    @property
    def image_shape(self) -> Tuple[int, int, int]:
        return self.images.shape[1:]

    def get_batch(self, indices: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Assemble a uint8 batch for the given sample indices.

        The common protocol between in-memory arrays and lazy ImageFolder-style
        datasets (tpu_dist.data.imagefolder); the loader only ever calls this.
        Uses the native row-gather library (csrc/gather.cpp) when built —
        whole-row memcpy with the GIL released, so batch assembly overlaps the
        device step; numpy fallback otherwise.
        """
        from tpu_dist import _native
        return _native.gather_batch(self.images, self.labels, indices)


# elements of noise drawn at a time by _synthetic: 16 MiB of float64, so the
# set's peak is its uint8 result plus some tens of MB whatever its size
_SYNTH_CHUNK_ELEMS = 1 << 21


def _synthetic(num: int, shape: Tuple[int, int, int], num_classes: int,
               proto_seed: int, sample_seed: int, name: str) -> ArrayDataset:
    """Deterministic learnable synthetic data: per-class low-frequency pattern
    + per-sample noise. Class prototypes depend only on ``proto_seed`` so the
    train and val splits share one distribution; samples/noise differ via
    ``sample_seed``. Class signal is strong enough that a CNN separates it in
    a few steps (used by convergence tests, SURVEY.md §4).

    The noise is drawn, added, clipped and written into the uint8 result a
    bounded chunk of rows at a time. A ``Generator`` fills sequentially, so
    chunked draws continue ONE stream: the arrays are bitwise those of a
    single ``rng.normal(size=(num, h, w, c))`` (3.2 GB of float64 and five
    more full-size temporaries for 131,072 CIFAR rows; 1.2 MB an image at
    224 x 224), whose cost was page faults, not arithmetic."""
    proto_rng = np.random.default_rng(proto_seed)
    rng = np.random.default_rng(sample_seed)
    h, w, c = shape
    # low-frequency class prototypes: 4x4 random grids, upsampled a chunk's
    # rows at a time (1000 classes at 224 x 224 would be 600 MB whole)
    protos = proto_rng.normal(0.0, 1.0, size=(num_classes, 4, 4, c)).astype(np.float32)
    # labels first, then the noise: the order of draws is the stream's
    labels = rng.integers(0, num_classes, size=num).astype(np.int32)
    images = np.empty((num, h, w, c), np.uint8)
    rows = max(1, _SYNTH_CHUNK_ELEMS // (h * w * c))
    for lo in range(0, num, rows):
        chunk = labels[lo:lo + rows]
        x = rng.normal(0.0, 0.6, size=(len(chunk), h, w, c)).astype(np.float32)
        x += np.repeat(np.repeat(protos[chunk], (h + 3) // 4, axis=1),
                       (w + 3) // 4, axis=2)[:, :h, :w, :]
        x += 3.0
        x /= 6.0
        np.clip(x, 0.0, 1.0, out=x)
        x *= 255
        images[lo:lo + rows] = x  # the float -> uint8 cast of astype
    mean = np.full((c,), 0.5, np.float32)
    std = np.full((c,), 0.25, np.float32)
    return ArrayDataset(images, labels, mean, std, num_classes, name)


def _load_cifar10_pickles(root: str) -> Optional[Tuple[ArrayDataset, ArrayDataset]]:
    d = os.path.join(root, "cifar-10-batches-py")
    if not os.path.isdir(d):
        return None
    def load(names):
        xs, ys = [], []
        for n in names:
            with open(os.path.join(d, n), "rb") as f:
                batch = pickle.load(f, encoding="bytes")
            xs.append(np.asarray(batch[b"data"], np.uint8))
            ys.append(np.asarray(batch[b"labels"], np.int32))
        x = np.concatenate(xs).reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
        return np.ascontiguousarray(x), np.concatenate(ys)
    xtr, ytr = load([f"data_batch_{i}" for i in range(1, 6)])
    xte, yte = load(["test_batch"])
    mk = lambda x, y, nm: ArrayDataset(x, y, CIFAR10_MEAN, CIFAR10_STD, 10, nm)
    return mk(xtr, ytr, "cifar10-train"), mk(xte, yte, "cifar10-val")


def _read_idx(path: str) -> np.ndarray:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        zero, dtype, ndim = struct.unpack(">HBB", f.read(4))
        shape = struct.unpack(">" + "I" * ndim, f.read(4 * ndim))
        return np.frombuffer(f.read(), np.uint8).reshape(shape)


def _load_mnist_idx(root: str) -> Optional[Tuple[ArrayDataset, ArrayDataset]]:
    candidates = [root, os.path.join(root, "MNIST", "raw")]
    for d in candidates:
        tri = os.path.join(d, "train-images-idx3-ubyte")
        if os.path.exists(tri) or os.path.exists(tri + ".gz"):
            def get(stem):
                p = os.path.join(d, stem)
                return _read_idx(p if os.path.exists(p) else p + ".gz")
            xtr = get("train-images-idx3-ubyte")[..., None]
            ytr = get("train-labels-idx1-ubyte").astype(np.int32)
            xte = get("t10k-images-idx3-ubyte")[..., None]
            yte = get("t10k-labels-idx1-ubyte").astype(np.int32)
            mk = lambda x, y, nm: ArrayDataset(x, y, MNIST_MEAN, MNIST_STD, 10, nm)
            return mk(xtr, ytr, "mnist-train"), mk(xte, yte, "mnist-val")
    return None


def load_dataset(name: str, root: str, synth_train: int = 50000,
                 synth_val: int = 10000, seed: int = 1234,
                 ) -> Tuple[ArrayDataset, ArrayDataset]:
    """Returns (train, val). Falls back to synthetic when files are absent."""
    name = name.lower()
    if name in ("cifar10", "synthetic", "synthetic-cifar10"):
        if name == "cifar10":
            real = _load_cifar10_pickles(root)
            if real is not None:
                return real
        tr = _synthetic(synth_train, (32, 32, 3), 10, seed, seed + 1, "synth-cifar10-train")
        va = _synthetic(synth_val, (32, 32, 3), 10, seed, seed + 2, "synth-cifar10-val")
        return tr, va
    if name in ("mnist", "synthetic-mnist"):
        if name == "mnist":
            real = _load_mnist_idx(root)
            if real is not None:
                return real
        tr = _synthetic(synth_train, (28, 28, 1), 10, seed, seed + 1, "synth-mnist-train")
        va = _synthetic(synth_val, (28, 28, 1), 10, seed, seed + 2, "synth-mnist-val")
        return tr, va
    if name == "imagenet":
        from tpu_dist.data.imagefolder import load_imagefolder
        real = load_imagefolder(root)
        if real is not None:
            return real
        tr = _synthetic(synth_train, (224, 224, 3), 1000, seed, seed + 1, "synth-imagenet-train")
        va = _synthetic(synth_val, (224, 224, 3), 1000, seed, seed + 2, "synth-imagenet-val")
        return tr, va
    raise ValueError(f"unknown dataset {name!r}")
