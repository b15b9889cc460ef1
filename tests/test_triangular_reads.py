"""The co-occurrence pass: exact counts from one device program and one
blocking device->host read, with nearby row counts sharing one executable."""
import jax
import numpy as np
import pytest

from repro.core import triangular
from repro.core.triangular import cooc_blocks, cooccurrence_counts

WORDS = 5


def _bitmaps(n, words=WORDS, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 2**32, (n, words), dtype=np.uint32)


def _reference(bm):
    """C[i, j] = sum_w popcount(B[i, w] & B[j, w]), in numpy alone."""
    inter = np.bitwise_and(bm[:, None, :], bm[None, :, :])
    return np.bitwise_count(inter).sum(-1, dtype=np.int64)


def _counting_reads(monkeypatch):
    reads = []
    device_get = jax.device_get

    def counting(x):
        reads.append(1)
        return device_get(x)
    monkeypatch.setattr(jax, "device_get", counting)
    return reads


@pytest.mark.parametrize("n", [1, 63, 64, 65, 465, 870])
def test_counts_equal_a_numpy_popcount_reference(n):
    bm = _bitmaps(n, seed=n)
    got = cooccurrence_counts(jax.device_put(bm))
    assert got.shape == (n, n) and got.dtype == np.int32
    np.testing.assert_array_equal(got, _reference(bm))


@pytest.mark.parametrize("on_device", [True, False])
def test_a_pass_makes_one_blocking_read(monkeypatch, on_device):
    bm = _bitmaps(465)
    arg = jax.device_put(bm) if on_device else bm
    cooccurrence_counts(arg)                      # compile outside the count
    reads = _counting_reads(monkeypatch)
    got = cooccurrence_counts(arg)
    monkeypatch.undo()
    assert len(reads) == cooc_blocks(465) == 1
    np.testing.assert_array_equal(got, _reference(bm))


def test_an_empty_pass_reads_nothing(monkeypatch):
    reads = _counting_reads(monkeypatch)
    got = cooccurrence_counts(np.zeros((0, WORDS), np.uint32))
    monkeypatch.undo()
    assert got.shape == (0, 0)
    assert len(reads) == cooc_blocks(0) == 0


def test_row_counts_in_one_power_of_two_bucket_share_one_program():
    words = 11                            # a width no other test compiles
    before = triangular._cooc_block._cache_size()
    for n in (65, 100, 128):              # all padded to 128 rows
        cooccurrence_counts(jax.device_put(_bitmaps(n, words)))
    assert triangular._cooc_block._cache_size() == before + 1
    cooccurrence_counts(jax.device_put(_bitmaps(129, words)))  # 256 rows
    assert triangular._cooc_block._cache_size() == before + 2
