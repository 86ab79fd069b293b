"""Native (C++) data-plane tests: the fedio kernels must reproduce the
pure-numpy reference pipelines exactly where the math is exact (pure
copies) and to float rounding where it is not (bilinear interpolation).

The build is exercised implicitly: ``native.lib()`` compiles fedio.cpp on
first use. If no compiler exists in the environment the whole module
skips — the numpy fallback is what every other test file runs on.
"""

import numpy as np
import pytest

from commefficient_tpu import native
from commefficient_tpu.data import transforms as T

pytestmark = pytest.mark.skipif(native.lib() is None,
                                reason="native fedio library unavailable")


def test_gather_rows_matches_fancy_indexing():
    rng = np.random.RandomState(0)
    src = rng.randint(0, 255, (64, 17, 3), np.uint8)
    idx = rng.randint(0, 64, 40)
    np.testing.assert_array_equal(native.gather_rows(src, idx), src[idx])
    fsrc = rng.randn(32, 5).astype(np.float32)
    np.testing.assert_array_equal(native.gather_rows(fsrc, idx % 32),
                                  fsrc[idx % 32])


def test_gather_rows_guards():
    """The C side is a raw memcpy: empty gathers must work and bad indices
    must raise (numpy semantics), never read out-of-buffer memory."""
    src = np.arange(12, dtype=np.float32).reshape(4, 3)
    out = native.gather_rows(src, np.array([], np.int64))
    assert out.shape == (0, 3)
    for bad in ([4], [-1]):
        with pytest.raises(IndexError):
            native.gather_rows(src, np.array(bad, np.int64))


def test_rrc_batch_matches_numpy_pipeline():
    rng_np = np.random.RandomState(7)
    rng_nat = np.random.RandomState(7)
    imgs = np.random.RandomState(1).randint(0, 256, (6, 64, 48, 3),
                                            np.uint8)
    mean, std = T.IMAGENET_MEAN, T.IMAGENET_STD
    numpy_fn = T.compose(T.random_resized_crop(32), T.random_hflip(),
                         T.normalize(mean, std))
    out_np = numpy_fn([imgs], rng_np)[0]

    fused = T.fused_rrc_train(mean, std, 32)
    out_nat = fused([imgs], rng_nat)[0]
    assert out_nat.shape == out_np.shape == (6, 32, 32, 3)
    # same crops/flips (same rng draws); bilinear differs only in float
    # evaluation order
    np.testing.assert_allclose(out_nat, out_np, atol=2e-4)


def test_rrc_consumes_same_rng_as_numpy():
    """After the fused pass, the rng must sit at the same position the
    numpy stages leave it (mid-epoch switching must not fork the stream)."""
    imgs = np.random.RandomState(1).randint(0, 256, (4, 40, 40, 3),
                                            np.uint8)
    rng_a, rng_b = np.random.RandomState(3), np.random.RandomState(3)
    T.compose(T.random_resized_crop(16), T.random_hflip(),
              T.normalize(T.IMAGENET_MEAN, T.IMAGENET_STD))([imgs], rng_a)
    T.fused_rrc_train(T.IMAGENET_MEAN, T.IMAGENET_STD, 16)([imgs], rng_b)
    assert rng_a.randint(1 << 30) == rng_b.randint(1 << 30)


@pytest.mark.parametrize("mode,fill,hflip_p", [("reflect", 0.0, 0.5),
                                               ("constant", 1.0, 0.0)])
def test_pad_crop_bit_identical_to_numpy(mode, fill, hflip_p):
    """The geometric kernels are pure copies — bit-equality, not allclose.
    Covers the CIFAR (reflect+flip) and EMNIST (constant-fill white, no
    flip) configurations."""
    mean = np.array([0.5], np.float32)
    std = np.array([0.25], np.float32)
    imgs = np.random.RandomState(2).randint(0, 256, (5, 28, 28, 1),
                                            np.uint8)
    aug = [T.random_crop(28, 2, mode, fill)]
    if hflip_p > 0:
        aug.append(T.random_hflip(hflip_p))
    numpy_fn = T.compose(T.normalize(mean, std), *aug)
    fused = T.PadCropTrain(mean, std, 28, 2, mode, fill, hflip_p)
    rng_a, rng_b = np.random.RandomState(9), np.random.RandomState(9)
    out_np = numpy_fn([imgs], rng_a)[0]
    out_nat = fused([imgs], rng_b)[0]
    np.testing.assert_array_equal(out_nat, out_np)


def test_thread_pool_parallel_and_concurrent_callers():
    """Force the multi-thread pool path (this CI box may report 1 CPU) and
    hammer it from several Python threads at once: results must match the
    serial path and the pool must not deadlock or corrupt a job."""
    import ctypes
    import threading

    h = native.lib()
    rng = np.random.RandomState(0)
    src = rng.randint(0, 255, (512, 33), np.uint8)
    row_bytes = src.shape[1]

    def gather(idx, nthreads):
        out = np.empty((len(idx), row_bytes), np.uint8)
        h.fedio_gather_rows(src, np.ascontiguousarray(idx, np.int64),
                            len(idx), row_bytes, out,
                            ctypes.c_int(nthreads))
        return out

    idx0 = rng.randint(0, 512, 300)
    np.testing.assert_array_equal(gather(idx0, 4), src[idx0])

    errs = []

    def worker(seed):
        r = np.random.RandomState(seed)
        for _ in range(50):
            idx = r.randint(0, 512, 257)
            if not np.array_equal(gather(idx, 4), src[idx]):
                errs.append(seed)
                return

    threads = [threading.Thread(target=worker, args=(s,)) for s in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errs == []


def test_cifar_train_pipeline_is_fused_and_matches():
    """The shipped cifar10_train_transforms (fused) vs an explicitly
    composed numpy pipeline on CIFAR-shaped data."""
    imgs = np.random.RandomState(4).randint(0, 256, (8, 32, 32, 3),
                                            np.uint8)
    labels = np.arange(8)
    numpy_fn = T.compose(T.normalize(T.CIFAR10_MEAN, T.CIFAR10_STD),
                         T.random_crop(32, 4, "reflect"), T.random_hflip())
    rng_a, rng_b = np.random.RandomState(11), np.random.RandomState(11)
    out_np = numpy_fn([imgs, labels], rng_a)
    out_nat = T.cifar10_train_transforms([imgs, labels], rng_b)
    np.testing.assert_array_equal(out_nat[0], out_np[0])
    np.testing.assert_array_equal(out_nat[1], labels)


ROUND_CASES = {
    # CIFAR: reflect padding and flips; EMNIST: constant fill of 1.0 in
    # normalized space, no flip (so no rand is drawn)
    "cifar": dict(image=(32, 32, 3), mean=T.CIFAR10_MEAN, std=T.CIFAR10_STD,
                  padding=4, mode="reflect", fill=0.0, hflip_p=0.5),
    "emnist": dict(image=(28, 28, 1), mean=T.FEMNIST_MEAN,
                   std=T.FEMNIST_STD, padding=2, mode="constant", fill=1.0,
                   hflip_p=0.0),
}


def _numpy_stages(case):
    aug = [T.random_crop(case["image"][0], case["padding"], case["mode"],
                         case["fill"])]
    if case["hflip_p"] > 0:
        aug.append(T.random_hflip(case["hflip_p"]))
    return T.compose(T.normalize(case["mean"], case["std"]), *aug)


def _pad_crop_train(case):
    return T.PadCropTrain(case["mean"], case["std"], case["image"][0],
                          case["padding"], case["mode"], case["fill"],
                          case["hflip_p"])


@pytest.mark.parametrize("hflip_p", [0.5, 0.0])
@pytest.mark.parametrize("n", [1, 7, 50])
def test_array_draw_is_the_scalar_stream(n, hflip_p, same_rng_state):
    """``PadCropTrain.draw`` against the numpy stages' own draws: two
    scalar randints an image, then one rand(n) unless nothing flips."""
    t = T.PadCropTrain(T.CIFAR10_MEAN, T.CIFAR10_STD, 32, 4,
                       hflip_p=hflip_p)
    rng_a, rng_b = np.random.RandomState(n), np.random.RandomState(n)
    want = np.zeros((n, 3), np.int32)
    for i in range(n):
        want[i, 0] = rng_a.randint(0, 9)
        want[i, 1] = rng_a.randint(0, 9)
    if hflip_p > 0:
        want[:, 2] = rng_a.rand(n) < hflip_p
    got = t.draw(rng_b, n)
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    assert same_rng_state(rng_a, rng_b)


@pytest.mark.parametrize("case", sorted(ROUND_CASES))
def test_pad_crop_round_is_the_numpy_stages_client_by_client(
        case, same_rng_state):
    """One pass over a round (uint8 rows where they lie, a 256 x C table,
    scattered slots) against normalize + random_crop + random_hflip run on
    each client's rows in turn: bit-equal, and the same generator state."""
    case = ROUND_CASES[case]
    image = case["image"]
    pools = [np.random.RandomState(c).randint(0, 256, (n,) + image)
             .astype(np.uint8) for c, n in enumerate((9, 4, 17))]
    # (client, rows) of a round's three workers, and their (W=4, B=6) slots
    picks = [(2, [16, 0, 3, 3, 8]), (0, [8, 1]), (1, [3, 2, 1, 0, 0, 2])]
    W, B = 4, 6
    slots = np.concatenate([w * B + np.arange(len(rows))
                            for w, (_, rows) in enumerate(picks)])

    rng_a, rng_b = np.random.RandomState(5), np.random.RandomState(5)
    stages = _numpy_stages(case)
    want = np.zeros((W, B) + image, np.float32)
    for w, (c, rows) in enumerate(picks):
        want[w, :len(rows)] = stages([pools[c][rows]], rng_a)[0]

    t = _pad_crop_train(case)
    params = np.concatenate([t.draw(rng_b, len(rows)) for _, rows in picks])
    src = np.concatenate([pools[c].ctypes.data
                          + np.asarray(rows) * int(np.prod(image))
                          for c, rows in picks])
    got = np.zeros((W, B) + image, np.float32)
    native.pad_crop_round(src, slots, params, t.table(), got, t.padding,
                          t.mode == "reflect", t.fill)
    np.testing.assert_array_equal(got, want)
    assert same_rng_state(rng_a, rng_b)
    if case["hflip_p"] == 0:
        assert not params[:, 2].any()


def test_pad_crop_round_guards():
    """The C side writes and reads raw addresses: slots and offsets are
    checked before the call."""
    t = _pad_crop_train(ROUND_CASES["emnist"])
    img = np.zeros((1, 28, 28, 1), np.uint8)
    src = np.array([img.ctypes.data], np.int64)
    out = np.zeros((2, 28, 28, 1), np.float32)
    ok = np.array([[0, 4, 0]], np.int32)
    native.pad_crop_round(src, np.array([1]), ok, t.table(), out, 2, False,
                          1.0)
    for slot in (2, -1):
        with pytest.raises(IndexError):
            native.pad_crop_round(src, np.array([slot]), ok, t.table(), out,
                                  2, False, 1.0)
    with pytest.raises(ValueError):
        native.pad_crop_round(src, np.array([0]),
                              np.array([[5, 0, 0]], np.int32), t.table(),
                              out, 2, False, 1.0)
    with pytest.raises(ValueError):
        native.pad_crop_round(src, np.array([0]), ok, t.table(),
                              out.astype(np.float64), 2, False, 1.0)
