"""Bitonic sort/merge/top-k networks over packed (dist, idx) keys.

These networks are the kernel's LSM+GMM stages *and* the engine's
packed merge, so they are tested directly against numpy oracles:
sortedness, multiset preservation, exact union-lowest-L merges, and
the lowest-index tie rule the rest of the stack relies on.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st
from repro.core.packedkey import (
    IDX_FILL,
    INT_BIG,
    dist_idx_less,
    gmm_merge,
    idx_bits_for,
    key_less,
    lsm_topk,
    next_pow2,
    pack_keys,
    unpack_keys,
)


def topk_keys(keys, k_pad):
    """Ascending lowest-``k_pad`` packed keys (the LSM's live lanes)."""
    (out,) = lsm_topk((keys,), k_pad, key_less, (INT_BIG,))
    return out[..., :k_pad]


def sort_keys(keys):
    """Full ascending sort: the LSM with one group as wide as the row."""
    return topk_keys(keys, keys.shape[-1])


def _rand_keys(rng, *shape, m=256):
    """Random packed keys with plenty of duplicate distances."""
    bits = idx_bits_for(m)
    d = rng.integers(0, 8, shape).astype(np.float32)  # few distinct dists
    idx = rng.integers(0, m, shape).astype(np.int32)
    return pack_keys(jnp.asarray(d), jnp.asarray(idx), bits), bits


def test_next_pow2():
    assert [next_pow2(v) for v in (0, 1, 2, 3, 4, 5, 8, 9, 17)] == [
        1, 1, 2, 4, 4, 8, 8, 16, 32]


def test_sort_keys_sorts_and_preserves_multiset():
    rng = np.random.default_rng(0)
    keys, _ = _rand_keys(rng, 3, 5, 64)
    out = np.asarray(sort_keys(keys))
    assert (np.diff(out, axis=-1) >= 0).all()
    np.testing.assert_array_equal(np.sort(np.asarray(keys), axis=-1), out)


def test_sort_keys_rejects_non_pow2():
    with pytest.raises(ValueError, match="power-of-two"):
        sort_keys(jnp.zeros((3,), jnp.int32))


def test_merge_sorted_is_lowest_l_of_union():
    """GMM: an ascending run merged with a descending list is the
    ascending lowest-L of their union."""
    rng = np.random.default_rng(1)
    a, _ = _rand_keys(rng, 4, 16)
    b, _ = _rand_keys(rng, 4, 16)
    a = jnp.sort(a, axis=-1)
    b = jnp.sort(b, axis=-1)
    (out,) = gmm_merge((a,), (b[..., ::-1],), 16, key_less)
    out = np.asarray(out)
    union = np.concatenate([np.asarray(a), np.asarray(b)], axis=-1)
    expect = np.sort(union, axis=-1)[..., :16]
    np.testing.assert_array_equal(out, expect)


def test_topk_keys_matches_numpy_partial_sort():
    rng = np.random.default_rng(2)
    for width in (1, 3, 8, 19, 32, 57, 100):
        keys, _ = _rand_keys(rng, 2, width)
        out = np.asarray(topk_keys(keys, 8))
        full = np.sort(
            np.concatenate(
                [np.asarray(keys),
                 np.full((2, max(0, 8 - width)), INT_BIG, np.int32)],
                axis=-1),
            axis=-1)
        np.testing.assert_array_equal(out, full[..., :8])


@pytest.mark.parametrize("width", [129, 300, 1024])
def test_lsm_topk_multi_chunk_either_direction(width):
    """Rows wider than one 128-lane chunk (a non-power-of-two chunk
    count included) reduce to the lowest k_pad in lanes [0, k_pad),
    ascending or descending."""
    rng = np.random.default_rng(width)
    keys, _ = _rand_keys(rng, 3, width, m=4096)
    want = np.sort(np.asarray(keys), axis=-1)[..., :16]
    for descending in (False, True):
        (out,) = lsm_topk((keys,), 16, key_less, (INT_BIG,),
                          descending=descending)
        assert out.shape == (3, 128)
        np.testing.assert_array_equal(
            np.asarray(out)[..., :16], want[..., ::-1] if descending else want)


def test_packed_ties_resolve_to_lowest_index():
    """All-equal distances: the sorted keys enumerate indices ascending
    (the lax.top_k tie rule, encoded in the packed integer order)."""
    bits = idx_bits_for(64)
    idx = jnp.asarray([7, 3, 5, 1, 6, 0, 2, 4], jnp.int32)
    keys = pack_keys(jnp.full((8,), 2.5, jnp.float32), idx, bits)
    _, got_idx = unpack_keys(sort_keys(keys), bits)
    np.testing.assert_array_equal(np.asarray(got_idx), np.arange(8))
    # and topk over a wider tied field picks the lowest indices
    idx_w = jnp.asarray(np.random.default_rng(3).permutation(40), jnp.int32)
    keys_w = pack_keys(jnp.full((40,), 1.0, jnp.float32), idx_w, bits)
    _, top_idx = unpack_keys(topk_keys(keys_w, 4), bits)
    np.testing.assert_array_equal(np.asarray(top_idx), np.arange(4))


def test_two_array_sort_ties_lowest_index():
    """The exact (unpacked) comparator path keeps the same tie rule."""
    d = jnp.asarray([1.0, 1.0, 0.5, 1.0], jnp.float32)
    i = jnp.asarray([9, 2, 11, 5], jnp.int32)
    sd, si = lsm_topk((d, i), 4, dist_idx_less, (np.inf, IDX_FILL))
    np.testing.assert_array_equal(np.asarray(si), [11, 2, 5, 9])
    np.testing.assert_allclose(np.asarray(sd), [0.5, 1.0, 1.0, 1.0])


def test_two_array_topk_fill_loses_ties():
    """IDX_FILL padding lanes lose every distance tie, so a real lane
    with distance == BIG-sentinel still beats padding."""
    d = jnp.asarray([3.0, 1.0, 2.0], jnp.float32)
    i = jnp.asarray([0, 1, 2], jnp.int32)
    td, ti = lsm_topk((d, i), 4, dist_idx_less, (np.float32(3.0), IDX_FILL))
    assert np.asarray(ti).tolist() == [1, 2, 0, IDX_FILL]
    np.testing.assert_allclose(np.asarray(td), [1.0, 2.0, 3.0, 3.0])


def test_two_array_merge_tracks_payload():
    """gmm_merge moves the idx payload in lockstep with the dist key:
    merged (dist, idx) pairs stay true pairs."""
    rng = np.random.default_rng(4)
    da = np.sort(rng.standard_normal((2, 8)).astype(np.float32), axis=-1)
    db = np.sort(rng.standard_normal((2, 8)).astype(np.float32), axis=-1)
    ia = np.arange(0, 8, dtype=np.int32) * 2 + np.zeros((2, 1), np.int32)
    ib = np.arange(0, 8, dtype=np.int32) * 2 + 1
    ib = np.broadcast_to(ib, (2, 8)).astype(np.int32)
    md, mi = gmm_merge(
        (jnp.asarray(da), jnp.asarray(ia)),
        (jnp.asarray(db[:, ::-1]), jnp.asarray(ib[:, ::-1])), 8,
        dist_idx_less)
    md, mi = np.asarray(md), np.asarray(mi)
    assert (np.diff(md, axis=-1) >= 0).all()
    # every output pair exists in the input pair set, per row
    for r in range(2):
        pairs_in = {(float(d), int(i)) for d, i in
                    list(zip(da[r], ia[r])) + list(zip(db[r], ib[r]))}
        for d, i in zip(md[r], mi[r]):
            assert (float(d), int(i)) in pairs_in
    # and they are the 8 smallest distances of the union
    np.testing.assert_allclose(
        md, np.sort(np.concatenate([da, db], axis=-1), axis=-1)[:, :8])


def test_networks_handle_batched_leading_dims():
    rng = np.random.default_rng(5)
    keys, _ = _rand_keys(rng, 2, 3, 4, 16)
    out = np.asarray(topk_keys(keys, 8))
    assert out.shape == (2, 3, 4, 8)
    expect = np.sort(np.asarray(keys), axis=-1)[..., :8]
    np.testing.assert_array_equal(out, expect)


def test_sort_keys_unique_distances_roundtrip():
    """Unique distances: sort_keys orders exactly like argsort on the
    float distances, and unpack recovers the permutation."""
    rng = np.random.default_rng(6)
    bits = idx_bits_for(128)
    d = rng.permutation(32).astype(np.float32)
    idx = jnp.arange(32, dtype=jnp.int32)
    keys = pack_keys(jnp.asarray(d), idx, bits)
    _, si = unpack_keys(sort_keys(keys), bits)
    np.testing.assert_array_equal(np.asarray(si), np.argsort(d, kind="stable"))


def test_comparators():
    a = (jnp.asarray([1.0, 2.0]), jnp.asarray([3, 1]))
    b = (jnp.asarray([2.0, 2.0]), jnp.asarray([0, 2]))
    np.testing.assert_array_equal(np.asarray(dist_idx_less(a, b)),
                                  [True, True])
    np.testing.assert_array_equal(
        np.asarray(key_less((jnp.asarray([3, 5]),), (jnp.asarray([4, 5]),))),
        [True, False])


@settings(deadline=None)
@given(
    dists=st.lists(st.integers(min_value=0, max_value=6),
                   min_size=1, max_size=70),
    kd=st.integers(min_value=1, max_value=16),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_property_topk_keys_matches_sort(dists, kd, seed):
    if not HAVE_HYPOTHESIS:  # pragma: no cover - shim path
        pytest.skip("hypothesis not installed")
    rng = np.random.default_rng(seed)
    m = 256
    bits = idx_bits_for(m)
    d = np.asarray(dists, np.float32)
    idx = rng.integers(0, m, len(dists)).astype(np.int32)
    keys = pack_keys(jnp.asarray(d), jnp.asarray(idx), bits)
    k_pad = next_pow2(kd)
    out = np.asarray(topk_keys(keys, k_pad))
    ref = np.sort(np.concatenate(
        [np.asarray(keys),
         np.full(max(0, k_pad - len(dists)), INT_BIG, np.int32)]))
    np.testing.assert_array_equal(out, ref[:k_pad])
