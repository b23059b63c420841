"""Dataset/loader/pipeline tests (reference C4/C13 equivalents)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist.data import (DataLoader, DistributedSampler, load_dataset,
                           make_transform, prefetch_to_device)
from tpu_dist.data.datasets import CIFAR10_MEAN, CIFAR10_STD


def test_synthetic_deterministic_and_learnable_split():
    tr1, va1 = load_dataset("synthetic-cifar10", "/nonexistent", 256, 64, seed=7)
    tr2, va2 = load_dataset("synthetic-cifar10", "/nonexistent", 256, 64, seed=7)
    np.testing.assert_array_equal(tr1.images, tr2.images)
    # train and val must share class structure (same prototypes, diff samples)
    assert not np.array_equal(tr1.images[:64], va1.images)
    assert tr1.images.shape == (256, 32, 32, 3)
    assert tr1.images.dtype == np.uint8


def test_loader_yields_full_uint8_batches():
    tr, _ = load_dataset("synthetic-mnist", "/nonexistent", 100, 10, seed=3)
    sampler = DistributedSampler(len(tr), 2, 0, shuffle=True, batch_size=16)
    loader = DataLoader(tr, sampler, 16)
    batches = list(loader)
    assert len(batches) == len(loader)
    for imgs, labels in batches:
        assert imgs.shape == (16, 28, 28, 1)
        assert imgs.dtype == np.uint8
        assert labels.shape == (16,)


def test_transform_matches_totensor_normalize():
    # ToTensor (/255) + Normalize(mean, std), reference 2.distributed.py:127-136
    img = np.full((1, 2, 2, 3), 128, np.uint8)
    t = make_transform(CIFAR10_MEAN, CIFAR10_STD)
    out = np.asarray(t(jnp.asarray(img)))
    expected = (128 / 255.0 - CIFAR10_MEAN) / CIFAR10_STD
    np.testing.assert_allclose(out[0, 0, 0], expected, rtol=1e-5)


def test_augmented_transform_preserves_shape_and_is_random():
    t = make_transform(np.zeros(3, np.float32), np.ones(3, np.float32),
                       augment=True, max_shift=2)
    img = np.random.default_rng(0).integers(0, 255, (4, 8, 8, 3)).astype(np.uint8)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    o1 = np.asarray(t(jnp.asarray(img), k1))
    o2 = np.asarray(t(jnp.asarray(img), k2))
    assert o1.shape == img.shape
    assert not np.array_equal(o1, o2)


def test_prefetch_to_device_preserves_order():
    batches = [(np.full((2, 2), i, np.uint8), np.array([i, i])) for i in range(5)]
    out = list(prefetch_to_device(iter(batches), None, size=3))
    assert len(out) == 5
    for i, (imgs, labels) in enumerate(out):
        assert int(np.asarray(imgs)[0, 0]) == i


def test_device_prefetcher_order_stats_and_clean_shutdown():
    """DevicePrefetcher (the round-9 double-buffered upload pipeline):
    batches arrive in order, the overlap ledger counts them, and
    exhaustion JOINS the producer thread (DL103's clean path, not just
    the daemon backstop)."""
    from tpu_dist.data.loader import DevicePrefetcher

    batches = [np.full((4,), i, np.int32) for i in range(7)]
    pf = DevicePrefetcher(iter(batches), depth=2)
    out = list(pf)
    assert [int(np.asarray(b)[0]) for b in out] == list(range(7))
    st = pf.stats()
    assert st["batches"] == 7 and st["put_s"] >= 0.0
    assert st["overlap_efficiency"] is None or 0.0 <= st["overlap_efficiency"] <= 1.0
    assert not pf._thread.is_alive()


def test_device_prefetcher_abandonment_stops_producer():
    """Breaking out of the consuming loop (generator close) must stop and
    join the producer — an epoch cut short never leaves an upload thread
    feeding a dead consumer."""
    from tpu_dist.data.loader import DevicePrefetcher

    def endless():
        i = 0
        while True:
            yield np.full((2,), i, np.int32)
            i += 1

    pf = DevicePrefetcher(endless(), depth=2)
    it = iter(pf)
    assert int(np.asarray(next(it))[0]) == 0
    assert int(np.asarray(next(it))[0]) == 1
    it.close()                      # consumer abandons mid-stream
    assert not pf._thread.is_alive()


def test_device_prefetcher_error_propagates_and_joins():
    from tpu_dist.data.loader import DevicePrefetcher

    def boom():
        yield np.zeros((2,), np.int32)
        raise RuntimeError("assembly failed")

    pf = DevicePrefetcher(boom())
    it = iter(pf)
    next(it)
    with pytest.raises(RuntimeError, match="assembly failed"):
        next(it)
    assert not pf._thread.is_alive()


def test_device_prefetcher_composes_with_sampler_epochs():
    """One prefetcher per epoch over the loader's stream: every epoch
    yields exactly len(loader) batches, set_epoch reshuffles between them
    (different batch content), and the same-epoch replay is bit-identical
    — the sampler/epoch logic needs no special casing in the prefetcher."""
    from tpu_dist.data.loader import DevicePrefetcher

    tr, _ = load_dataset("synthetic-mnist", "/nonexistent", 64, 10, seed=5)
    sampler = DistributedSampler(len(tr), 1, 0, shuffle=True, batch_size=16)
    loader = DataLoader(tr, sampler, 16)

    def epoch_batches(epoch):
        sampler.set_epoch(epoch)
        pf = DevicePrefetcher(iter(loader), depth=2)
        out = [np.asarray(imgs) for imgs, _ in pf]
        assert not pf._thread.is_alive()
        return out

    e0, e1, e0_again = (epoch_batches(0), epoch_batches(1),
                        epoch_batches(0))
    assert len(e0) == len(e1) == len(loader)
    assert any(not np.array_equal(a, b) for a, b in zip(e0, e1))
    assert all(np.array_equal(a, b) for a, b in zip(e0, e0_again))


def test_stream_prefetch_passes_none_and_exception_items():
    """Tagged control envelopes (ADVICE r3): a producer may legitimately
    yield None or exception INSTANCES as items — neither truncates the
    stream nor raises — while a raising producer still propagates."""
    from tpu_dist.data.loader import stream_prefetch

    items = [1, None, ValueError("payload, not control"), 4]
    out = list(stream_prefetch(iter(items)))
    assert out[0] == 1 and out[1] is None and out[3] == 4
    assert isinstance(out[2], ValueError)

    def boom():
        yield 1
        raise RuntimeError("assembly failed")

    got = []
    try:
        for x in stream_prefetch(boom()):
            got.append(x)
        raised = False
    except RuntimeError:
        raised = True
    assert raised and got == [1]


def test_stream_prefetch_waits_for_its_producer_when_abandoned():
    """A consumer that leaves early (a preemption snapshot leaves the
    epoch by SystemExit) must not leave the producer running: a daemon
    thread still inside device_put when the interpreter exits aborts the
    process (SIGABRT in place of the snapshot's exit code). Closing the
    generator stops the producer AND joins it."""
    import threading
    import time

    from tpu_dist.data.loader import stream_prefetch

    in_item = threading.Event()
    finished = threading.Event()

    def slow():
        for i in range(100):
            if i == 2:              # staged behind item 1: nobody waits
                in_item.set()
                time.sleep(0.3)     # "inside device_put"
                finished.set()
            yield i

    before = set(threading.enumerate())
    gen = stream_prefetch(slow(), depth=1)
    assert next(gen) == 0
    assert in_item.wait(timeout=10.0)
    gen.close()                     # what unwinding the loop does
    assert finished.is_set(), "close() returned with the producer mid-item"
    assert set(threading.enumerate()) <= before, "a producer outlived it"


def test_token_bin_size_alignment_checked(tmp_path):
    """A .bin whose byte size is not a whole number of tokens for the
    configured dtype fails loudly instead of yielding garbage ids."""
    import os

    import pytest

    from tpu_dist.data.tokens import _load_stream

    p = tmp_path / "odd.bin"
    p.write_bytes(b"\x01\x02\x03")  # 3 bytes: not divisible by uint16
    with pytest.raises(ValueError, match="whole number"):
        _load_stream(str(p))
    os.environ["TPU_DIST_TOKEN_DTYPE"] = "uint32"
    try:
        q = tmp_path / "ok16.bin"
        q.write_bytes(np.arange(6, dtype=np.uint16).tobytes())  # 12 bytes
        arr, _ = _load_stream(str(q))  # 4-aligned: loads as uint32
        assert arr.dtype == np.uint32
    finally:
        del os.environ["TPU_DIST_TOKEN_DTYPE"]


def test_loader_propagates_worker_errors():
    class Bad:
        def get_batch(self, idx):
            raise RuntimeError("decode failed")

    sampler = DistributedSampler(32, 1, 0, batch_size=8)
    loader = DataLoader(Bad(), sampler, 8)
    try:
        list(loader)
        raised = False
    except RuntimeError:
        raised = True
    assert raised


def _synthetic_one_shot(num, shape, num_classes, proto_seed, sample_seed):
    """The form ``_synthetic`` had until it drew in chunks: the whole set's
    noise from one ``rng.normal`` call, five full-size temporaries."""
    proto_rng = np.random.default_rng(proto_seed)
    rng = np.random.default_rng(sample_seed)
    h, w, c = shape
    protos = proto_rng.normal(
        0.0, 1.0, size=(num_classes, 4, 4, c)).astype(np.float32)
    protos = np.repeat(np.repeat(protos, (h + 3) // 4, axis=1),
                       (w + 3) // 4, axis=2)[:, :h, :w, :]
    labels = rng.integers(0, num_classes, size=num).astype(np.int32)
    noise = rng.normal(0.0, 0.6, size=(num, h, w, c)).astype(np.float32)
    imgs = np.clip((protos[labels] + noise + 3.0) / 6.0, 0.0, 1.0)
    return (imgs * 255).astype(np.uint8), labels


@pytest.mark.parametrize("shape,classes,num", [
    ((32, 32, 3), 10, 1500),     # CIFAR: 682 rows a chunk
    ((28, 28, 1), 10, 6001),     # MNIST: 2674
    ((224, 224, 3), 50, 31),     # ImageNet: 13 (the one-shot form's
                                 # upsampled prototypes: 0.6 MB a class)
])
def test_synthetic_equals_the_one_shot_form_bitwise(shape, classes, num):
    from tpu_dist.data.datasets import _SYNTH_CHUNK_ELEMS, _synthetic

    rows = _SYNTH_CHUNK_ELEMS // int(np.prod(shape))
    assert rows >= 1 and num > rows and num % rows  # a ragged last chunk
    ds = _synthetic(num, shape, classes, 5, 6, "synth")
    images, labels = _synthetic_one_shot(num, shape, classes, 5, 6)
    assert ds.images.dtype == np.uint8 and ds.labels.dtype == np.int32
    np.testing.assert_array_equal(ds.labels, labels)
    np.testing.assert_array_equal(ds.images, images)
    assert ds.images.std() > 20  # noise and prototypes, not a constant


def test_synthetic_peak_allocation_is_the_result_plus_a_few_chunks():
    import tracemalloc

    from tpu_dist.data.datasets import _SYNTH_CHUNK_ELEMS, _synthetic

    num, shape = 16384, (32, 32, 3)   # one-shot: 403 MB of float64 noise
    tracemalloc.start()
    try:
        ds = _synthetic(num, shape, 10, 5, 6, "synth")
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    chunk_f64 = 8 * _SYNTH_CHUNK_ELEMS
    assert ds.images.nbytes == num * 3072
    # a chunk holds its float64 draw, the float32 cast and the gathered
    # prototypes at once: two chunks' worth of float64; three is the bound
    assert peak - ds.images.nbytes < 3 * chunk_f64, peak
