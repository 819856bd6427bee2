"""Streaming-engine correctness: merge strategies, two-level tiling,
packed keys, and DIGC state threaded through the served forward.

The exact merges ("select", "topk") must match the reference oracle
bit-for-bit on indices; the packed merge is tie-tolerant (distances
truncated by ``idx_bits`` mantissa bits) and is validated semantically:
the distances *implied by its chosen indices* must match the oracle's
distances within the truncation tolerance. Property tests run under the
shared hypothesis shim (skip cleanly when hypothesis is absent)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import BIG, DigcSpec, digc, digc_reference, pairwise_sq_dists
from repro.core.digc import merge_topk
from repro.core.engine import (
    CONODE_TILE_ALIGN,
    CONODE_TILE_BUDGET,
    CONODE_TILE_FLOOR,
    conode_tile,
    merge_packed_xla,
    select_topkd,
    stream_topk,
)
from repro.core.packedkey import idx_bits_for, pack_keys, unpack_keys


def _rand(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def assert_same_valid(i_a, d_a, i_b, d_b, rtol=1e-5, atol=1e-4):
    va = np.asarray(d_a) < BIG / 2
    vb = np.asarray(d_b) < BIG / 2
    np.testing.assert_array_equal(va, vb)
    np.testing.assert_array_equal(
        np.where(va, np.asarray(i_a), -1), np.where(vb, np.asarray(i_b), -1)
    )
    np.testing.assert_allclose(
        np.where(va, np.asarray(d_a), 0.0), np.where(vb, np.asarray(d_b), 0.0),
        rtol=rtol, atol=atol,
    )


# ---------------------------------------------------------------------------
# select_topkd: the grouped LSM


@pytest.mark.parametrize("group_w", [32, 64, 48])
@pytest.mark.parametrize("w,kd", [(64, 4), (200, 9), (1000, 16), (7, 7)])
def test_select_topkd_matches_lax_topk(w, kd, group_w):
    rng = np.random.default_rng(w * 31 + kd)
    d = _rand(rng, 2, 37, w) * 10
    vals, cols = select_topkd(d, kd, group_w=group_w)
    neg, ref_cols = jax.lax.top_k(-d, kd)
    np.testing.assert_array_equal(np.asarray(cols), np.asarray(ref_cols))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(-neg))


@pytest.mark.parametrize("group_w", [32, 64])
def test_select_topkd_ties_lowest_column(group_w):
    d = jnp.asarray([[3.0, 1.0, 1.0, 2.0, 1.0]])
    vals, cols = select_topkd(d, 4, group_w=group_w)
    np.testing.assert_array_equal(np.asarray(cols[0]), [1, 2, 4, 3])


def test_select_topkd_w64_ties_across_mask_words():
    """Equal values on both sides of the 32-lane word boundary of one
    64-lane group: extraction order must stay lowest-column-first and
    the second mask word must retire lanes 32..63 correctly."""
    row = np.full(64, 50.0, np.float32)
    row[[2, 34, 40]] = 1.0  # tie triple spanning both words
    row[[5, 63]] = 2.0
    vals, cols = select_topkd(jnp.asarray(row[None]), 5, group_w=64)
    np.testing.assert_array_equal(np.asarray(cols[0]), [2, 34, 40, 5, 63])
    np.testing.assert_array_equal(
        np.asarray(vals[0]), [1.0, 1.0, 1.0, 2.0, 2.0]
    )


def test_engine_group_w_knob_exact_end_to_end():
    """blocked merge="select" with group_w=64 == reference, through the
    registry (DigcSpec knob) and under query tiling."""
    rng = np.random.default_rng(77)
    x, y = _rand(rng, 2, 50, 12), _rand(rng, 2, 150, 12)
    i_r = digc(x, y, k=5, impl="reference")
    i_w = digc(x, y, k=5, impl="blocked", merge="select", group_w=64,
               block_n=16, block_m=96)
    np.testing.assert_array_equal(np.asarray(i_r), np.asarray(i_w))
    with pytest.raises(ValueError, match="group_w"):
        digc(x, y, k=5, impl="blocked", group_w=128)


def test_select_topkd_short_rows_pad_big():
    """Rows with fewer candidates than kd pad with BIG lanes."""
    d = jnp.asarray([[5.0, 4.0]])
    vals, _ = select_topkd(d, 4)
    v = np.asarray(vals[0])
    assert list(v[:2]) == [4.0, 5.0]
    assert np.all(v[2:] >= BIG / 2)


# ---------------------------------------------------------------------------
# Packed keys: pack/unpack + XLA packed merge vs merge_topk


@pytest.mark.parametrize("m", [8, 196, 3136, 1 << 20])
def test_pack_unpack_roundtrip_order(m):
    rng = np.random.default_rng(m % 97)
    bits = idx_bits_for(m)
    d = jnp.asarray(rng.standard_normal(256).astype(np.float32)) * 50
    idx = jnp.asarray(rng.integers(0, m, 256), jnp.int32)
    keys = pack_keys(d, idx, bits)
    d2, i2 = unpack_keys(keys, bits)
    np.testing.assert_array_equal(np.asarray(i2), np.asarray(idx))
    # truncation error bounded by 2^-(23 - idx_bits) relative
    np.testing.assert_allclose(
        np.asarray(d2), np.asarray(d), rtol=2.0 ** -(23 - bits) * 1.01,
        atol=1e-30,
    )
    # packed integer order == distance order where distances differ
    order_keys = np.argsort(np.asarray(keys), kind="stable")
    d_sorted = np.asarray(d)[order_keys]
    assert np.all(np.diff(d_sorted) >= -np.abs(d_sorted[1:]) * 2.0 ** -(23 - bits) * 2)


def test_idx_bits_cap():
    with pytest.raises(ValueError, match="at most"):
        idx_bits_for((1 << 20) + 1)


@settings(max_examples=30, deadline=None)
@given(
    kd=st.integers(1, 8),
    bw=st.integers(1, 40),
    m=st.integers(41, 300),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_packed_merge_matches_merge_topk(kd, bw, m, seed):
    """Packed-key merge == merge_topk bit-for-bit on idx (and within fp
    tolerance on dist) whenever distances survive truncation exactly —
    here: distinct small integers, exactly representable in the top
    23 - idx_bits mantissa bits."""
    rng = np.random.default_rng(seed)
    bits = idx_bits_for(m)
    n = 5
    width = kd + bw
    # distinct integer distances < 2^10: exact under <= 13 dropped bits
    vals = rng.permutation(1 << 10)[: n * width].astype(np.float32)
    cand_d = jnp.asarray(vals.reshape(n, width))
    cand_i = jnp.asarray(rng.integers(0, m, (n, width)), jnp.int32)
    run_d, blk_d = cand_d[:, :kd], cand_d[:, kd:]
    run_i, blk_i = cand_i[:, :kd], cand_i[:, kd:]
    # merge_topk expects a sorted running list (engine invariant)
    order = jnp.argsort(run_d, axis=1)
    run_d = jnp.take_along_axis(run_d, order, axis=1)
    run_i = jnp.take_along_axis(run_i, order, axis=1)

    ref_d, ref_i = merge_topk(run_d, run_i, blk_d, blk_i, kd)
    keys = merge_packed_xla(
        pack_keys(run_d, run_i, bits), pack_keys(blk_d, blk_i, bits), kd
    )
    got_d, got_i = unpack_keys(keys, bits)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(ref_i))
    np.testing.assert_allclose(
        np.asarray(got_d), np.asarray(ref_d), rtol=2.0 ** -(23 - bits) * 1.01
    )


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(2, 40),
    m=st.integers(4, 80),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_select_merge_equals_reference(n, m, seed):
    """Engine select merge == reference, bit-for-bit idx, random floats."""
    rng = np.random.default_rng(seed)
    k = min(4, m)
    x, y = _rand(rng, n, 7), _rand(rng, m, 7)
    i_r, d_r = digc_reference(x, y, k=k, return_dists=True)
    i_s, d_s = digc(x, y, k=k, impl="blocked", merge="select", block_m=16,
                    return_dists=True)
    assert_same_valid(i_r, d_r, i_s, d_s)


# ---------------------------------------------------------------------------
# Full engine paths: merge strategies x tiling, ragged edges


@pytest.mark.parametrize("merge", [None, "select", "topk"])
@pytest.mark.parametrize("block_n,block_m", [(None, 16), (16, 32), (13, 17)])
def test_engine_exact_merges_match_reference(merge, block_n, block_m):
    rng = np.random.default_rng(hash((merge, block_n, block_m)) % 2**31)
    x, y = _rand(rng, 2, 50, 12), _rand(rng, 2, 70, 12)
    i_r, d_r = digc(x, y, k=5, impl="reference", return_dists=True)
    i_e, d_e = digc(x, y, k=5, impl="blocked", merge=merge,
                    block_n=block_n, block_m=block_m, return_dists=True)
    np.testing.assert_array_equal(np.asarray(i_r), np.asarray(i_e))
    np.testing.assert_allclose(np.asarray(d_r), np.asarray(d_e),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("merge", [None, "select", "topk", "packed"])
def test_engine_query_tiled_causal_ragged(merge):
    """causal masking with N % block_n != 0: global row offsets must
    stay correct across query tiles."""
    rng = np.random.default_rng(21)
    x = _rand(rng, 2, 37, 8)  # 37 % 16 != 0
    i_r, d_r = digc(x, k=4, causal=True, impl="reference", return_dists=True)
    i_e, d_e = digc(x, k=4, causal=True, impl="blocked", merge=merge,
                    block_n=16, block_m=16, return_dists=True)
    va = np.asarray(d_r) < BIG / 2
    vb = np.asarray(d_e) < BIG / 2
    np.testing.assert_array_equal(va, vb)
    if merge == "packed":  # tie-tolerant: check implied distances
        np.testing.assert_allclose(
            np.where(vb, np.asarray(d_e), 0.0), np.where(va, np.asarray(d_r), 0.0),
            rtol=1e-3, atol=1e-3,
        )
    else:
        np.testing.assert_array_equal(
            np.where(va, np.asarray(i_r), -1), np.where(vb, np.asarray(i_e), -1)
        )


@pytest.mark.parametrize("merge", [None, "select", "topk"])
def test_engine_query_tiled_pos_bias_ragged(merge):
    rng = np.random.default_rng(22)
    x, y = _rand(rng, 2, 37, 8), _rand(rng, 2, 53, 8)
    p = _rand(rng, 2, 37, 53) * 0.3
    i_r = digc(x, y, k=4, impl="reference", pos_bias=p)
    i_e = digc(x, y, k=4, impl="blocked", merge=merge, pos_bias=p,
               block_n=16, block_m=16)
    np.testing.assert_array_equal(np.asarray(i_r), np.asarray(i_e))


def _edge_case(case):
    """(x, y, kd, block_m, m_valid) of one edge of the per-tile LSM."""
    rng = np.random.default_rng(31)
    if case == "kd_over_block_m":  # tiles narrower than kd, nb_m > 1
        return _rand(rng, 2, 40, 8), _rand(rng, 2, 70, 8), 20, 16, None
    if case == "kd_eq_m":
        return _rand(rng, 2, 30, 8), _rand(rng, 2, 24, 8), 24, 16, None
    if case == "duplicate_conodes":  # exact ties: lowest column first
        y = _rand(rng, 2, 12, 8)
        return _rand(rng, 2, 30, 8), jnp.concatenate([y] * 5, 1), 9, 16, None
    valid = np.arange(70) < 53  # pad co-node columns
    return _rand(rng, 2, 40, 8), _rand(rng, 2, 70, 8), 6, 32, jnp.asarray(valid)


@pytest.mark.parametrize(
    "case", ["kd_over_block_m", "kd_eq_m", "duplicate_conodes", "m_valid"])
def test_engine_default_select_and_reference_agree_bitwise(case):
    """At the LSM's edges merge=None (one lax.top_k per tile) and
    merge="select" give bit-identical indices and distances, and the
    reference's indices. (The reference sums the distance's terms in
    another order, so its distances agree to rounding.)"""
    x, y, kd, block_m, m_valid = _edge_case(case)
    i_r, d_r = digc_reference(x, y, k=kd, return_dists=True, m_valid=m_valid)
    i_t, d_t = digc(x, y, k=kd, impl="blocked", block_m=block_m,
                    m_valid=m_valid, return_dists=True)
    i_s, d_s = digc(x, y, k=kd, impl="blocked", merge="select",
                    block_m=block_m, m_valid=m_valid, return_dists=True)
    np.testing.assert_array_equal(np.asarray(i_t), np.asarray(i_s))
    np.testing.assert_array_equal(np.asarray(d_t), np.asarray(d_s))
    np.testing.assert_array_equal(np.asarray(i_t), np.asarray(i_r))
    np.testing.assert_allclose(np.asarray(d_t), np.asarray(d_r),
                               rtol=1e-5, atol=1e-4)


def test_engine_packed_full_path_tie_tolerant():
    """blocked merge="packed" vs reference: the distances implied by the
    chosen indices must match the oracle's within truncation tolerance
    (indices may differ only across truncation-ties)."""
    rng = np.random.default_rng(23)
    x, y = _rand(rng, 2, 40, 8), _rand(rng, 2, 70, 8)
    i_p, d_p = digc(x, y, k=5, impl="blocked", merge="packed", block_m=32,
                    return_dists=True)
    i_r, d_r = digc(x, y, k=5, impl="reference", return_dists=True)
    d_full = np.asarray(pairwise_sq_dists(x, y))
    implied = np.take_along_axis(d_full, np.asarray(i_p), axis=-1)
    bits = idx_bits_for(96)  # padded co-node count
    np.testing.assert_allclose(
        implied, np.asarray(d_r), rtol=2.0 ** -(23 - bits) * 4, atol=1e-3
    )


def test_engine_fuse_norms_and_bf16_tie_tolerant():
    rng = np.random.default_rng(24)
    x, y = _rand(rng, 2, 40, 16), _rand(rng, 2, 64, 16)
    i_r, d_r = digc(x, y, k=5, impl="reference", return_dists=True)
    d_full = np.asarray(pairwise_sq_dists(x, y))
    i_f, d_f = digc(x, y, k=5, impl="blocked", fuse_norms=True, block_m=32,
                    return_dists=True)
    implied = np.take_along_axis(d_full, np.asarray(i_f), axis=-1)
    np.testing.assert_allclose(implied, np.asarray(d_r), rtol=1e-5, atol=1e-4)
    i_b, _ = digc(x, y, k=5, impl="blocked", mxu_bf16=True, block_m=32,
                  return_dists=True)
    implied = np.take_along_axis(d_full, np.asarray(i_b), axis=-1)
    # bf16 contraction: ~8-bit mantissa on the cross term
    np.testing.assert_allclose(implied, np.asarray(d_r), rtol=0.1, atol=0.3)


def test_engine_dilation_through_spec():
    rng = np.random.default_rng(25)
    x = _rand(rng, 30, 8)
    spec = DigcSpec(impl="blocked", k=3, dilation=2, merge="select",
                    block_n=8, block_m=8)
    i_e = digc(x, spec=spec)
    i_r = digc(x, k=3, dilation=2, impl="reference")
    np.testing.assert_array_equal(np.asarray(i_r), np.asarray(i_e))


def test_stream_topk_self_graph_shares_norms():
    """y=None (self-graph) must equal passing x explicitly as y."""
    rng = np.random.default_rng(26)
    x = _rand(rng, 2, 33, 8)
    d_a, i_a = stream_topk(x, None, kd=4, block_m=16)
    d_b, i_b = stream_topk(x, x, kd=4, block_m=16)
    np.testing.assert_array_equal(np.asarray(i_a), np.asarray(i_b))
    np.testing.assert_array_equal(np.asarray(d_a), np.asarray(d_b))


@pytest.mark.parametrize("b,n,m,block_m,want", [
    (1, 196, 196, None, 196),  # 224 px isotropic: one tile
    (8, 196, 196, None, 196),
    (8, 3136, 196, None, 196),  # 224 px pyramid stage 0: 19.7 MB
    (1, 784, 784, None, 784),  # 448 px: one tile, not four of 256
    (8, 784, 784, None, 784),
    (8, 3136, 3136, None, 640),  # 896 px isotropic: 5 equal tiles
    (8, 50176, 3136, None, 256),  # 896 px pyramid stage 0: the floor
    (8, 784, 784, 256, 256),  # an explicit width is kept
    (8, 196, 196, 256, 196),  # ... clamped to M
    (2, 784, 784, 100, 100),
])
def test_conode_tile_sizes_from_the_shape(b, n, m, block_m, want):
    width = conode_tile(b, n, m, block_m)
    assert width == want
    if block_m is None and width < m:
        assert width % CONODE_TILE_ALIGN == 0
        assert width >= CONODE_TILE_FLOOR
        tiles = -(-m // width)
        assert b * n * -(-m // (tiles - 1)) * 4 > CONODE_TILE_BUDGET
    # a query tile of block_n rows sizes the co-node tile by its rows
    assert conode_tile(b, n * 4, m, block_m, block_n=n) == want


@pytest.mark.parametrize("b", [1, 2])
@pytest.mark.parametrize("kd", [36, 72, 108])
def test_engine_default_tile_matches_four_tiles_and_reference(kd, b):
    """At 448 px (N = M = 784) the default co-node tile is the whole
    M: its lists equal four tiles of 256 and a second top-k, and the
    reference tier's, bit for bit. Integer features make every distance
    exact whatever order a backend's matmul sums in (a CPU GEMM blocks
    a 784-wide tile unlike a 256-wide one), and give thousands of exact
    ties, which each path must break to the lowest column."""
    rng = np.random.default_rng(kd * 10 + b)
    x = jnp.asarray(rng.integers(-4, 5, (b, 784, 192)), jnp.float32)
    i_1, d_1 = digc(x, k=kd, impl="blocked", return_dists=True)
    i_4, d_4 = digc(x, k=kd, impl="blocked", block_m=256, return_dists=True)
    i_r, d_r = digc(x, k=kd, impl="reference", return_dists=True)
    assert (np.diff(np.asarray(d_1), axis=-1) == 0).any()
    for i, d in ((i_4, d_4), (i_r, d_r)):
        np.testing.assert_array_equal(np.asarray(i_1), np.asarray(i))
        np.testing.assert_array_equal(np.asarray(d_1), np.asarray(d))


def test_engine_unknown_merge_raises():
    rng = np.random.default_rng(27)
    x = _rand(rng, 10, 4)
    with pytest.raises(ValueError, match="unknown merge"):
        digc(x, k=3, impl="blocked", merge="bogus")


# ---------------------------------------------------------------------------
# DIGC state across calls and requests


def test_cache_cluster_warm_start_recall():
    """Warm-started cluster construction (a DigcState entry carrying the
    previous call's centroids) stays at cold-start recall."""
    from repro.core import DigcState, state_entry
    from repro.core.strategies import recall_vs_exact

    rng = np.random.default_rng(30)
    x = _rand(rng, 2, 128, 16)
    spec = DigcSpec(impl="cluster", k=4, n_clusters=8, n_probe=8,
                    capacity_factor=8.0)
    st = DigcState.init({"layer0": state_entry(centroids_shape=(2, 8, 16))})
    i_cold, st = digc(x, spec=spec, state=st, state_key="layer0")
    assert st.steps() == {"layer0": 1}
    i_warm, st = digc(x, spec=spec, state=st, state_key="layer0")
    assert st.steps() == {"layer0": 2}
    # full probe + ample capacity: both must be exact
    assert recall_vs_exact(x, x, i_cold, 4) == 1.0
    assert recall_vs_exact(x, x, i_warm, 4) == 1.0


def test_vig_serve_engine_persists_state():
    """VigServeEngine: the cluster tier serves through the compiled
    forward with functional DigcState carried across requests."""
    from repro.models import vig
    from repro.models.module import init_params
    from repro.serve.engine import VigServeEngine

    cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
        image_size=32, embed_dims=(16,), depths=(2,), num_classes=3, k=3,
        digc_impl="cluster",
    )
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    eng = VigServeEngine(cfg, params, autotune=False)
    imgs = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    out = eng.infer(imgs)
    assert out.shape == (2, 3) and bool(jnp.all(jnp.isfinite(out)))
    eng.infer(imgs)
    s = eng.stats()
    assert s["requests_served"] == 4
    # 2 blocks x 2 requests threaded the stage-0 state entry 4 times
    # (layer 2 warm-starts from layer 1, request 2 from request 1).
    assert s["digc_state"][2]["stage0"] == 4


def test_vig_serve_engine_eager_shim_matches_jit():
    """infer() on the cluster tier equals vig_forward with the DigcState
    threaded by hand, request over request (cold k-means on the first,
    warm start from the carried centroids on the second)."""
    from repro.models import vig
    from repro.models.module import init_params
    from repro.serve.engine import VigServeEngine

    cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
        image_size=32, embed_dims=(16,), depths=(2,), num_classes=3, k=3,
        digc_impl="cluster",
    )
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    imgs = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    eng = VigServeEngine(cfg, params, autotune=False)
    st = vig.init_vig_state(cfg, 2, "cluster")
    for _ in range(2):
        out_eng = eng.infer(imgs)
        out_ref, st = vig.vig_forward(params, imgs, cfg,
                                      digc_impl="cluster", state=st)
        np.testing.assert_allclose(
            np.asarray(out_eng), np.asarray(out_ref), rtol=1e-4, atol=1e-4
        )
    assert eng.state_steps()[2] == st.steps() == {"stage0": 4}


def test_vig_serve_engine_state_bounded_over_requests():
    """The served DigcState does not grow: after 5 infer() calls its
    pytree has the shapes, dtypes and byte count it had at init."""
    from repro.models import vig
    from repro.models.module import init_params
    from repro.serve.engine import VigServeEngine

    cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
        image_size=32, embed_dims=(16,), depths=(2,), num_classes=3, k=3,
        digc_impl="cluster",
    )
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    eng = VigServeEngine(cfg, params, autotune=False)

    def layout(state):
        leaves = jax.tree_util.tree_leaves(state)
        return ([(tuple(v.shape), v.dtype) for v in leaves],
                sum(v.nbytes for v in leaves))

    init = layout(vig.init_vig_state(cfg, 2, eng._impl_choice()))
    for seed in range(5):
        eng.infer(jax.random.normal(jax.random.PRNGKey(seed), (2, 32, 32, 3)))
    assert eng.state_steps()[2] == {"stage0": 10}
    assert layout(eng._compiled[2][1]) == init


def test_vig_serve_engine_autotunes_blocked(tmp_path):
    """warmup() now tunes a per-stage VigSchedule (host-keyed cache)."""
    from repro.models import vig
    from repro.models.module import init_params
    from repro.serve.engine import VigServeEngine

    cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
        image_size=32, embed_dims=(16,), depths=(1,), num_classes=3, k=3,
    )
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    eng = VigServeEngine(cfg, params, batch=2,
                         tuner_path=tmp_path / "tune.json")
    imgs = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    out = eng.infer(imgs)
    assert bool(jnp.all(jnp.isfinite(out)))
    st = eng.stats()
    assert [r["source"] for r in st["tuned"]] == ["measured"]
    assert len(st["schedule"]) == 1
    assert eng.schedule.spec_for(0).merge in ("select", "topk")


def test_vig_serve_engine_accepts_pretuned_schedule():
    """A VigSchedule passed as digc_impl must be used per stage (not
    collapsed to stage 0) and must never be clobbered by warmup."""
    from repro.core.tuner import VigSchedule
    from repro.models import vig
    from repro.models.module import init_params
    from repro.serve.engine import VigServeEngine

    cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
        image_size=32, embed_dims=(16,), depths=(1,), num_classes=3, k=3,
    )
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    sched = VigSchedule(stages=(
        DigcSpec(impl="blocked", k=3, block_m=32, merge="topk"),
    ))
    eng = VigServeEngine(cfg, params, digc_impl=sched, batch=2)
    assert eng.schedule is sched
    assert eng.warmup() is None  # pre-tuned: nothing to measure
    imgs = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    out = eng.infer(imgs)
    assert bool(jnp.all(jnp.isfinite(out)))
    assert eng.schedule is sched  # infer() did not re-tune over it


def test_vig_serve_engine_eager_blocked_uses_tuned_schedule(tmp_path,
                                                           monkeypatch):
    """After warmup() the jitted infer() compiles its forward through
    the tuned per-stage schedule, so warmup's measurement is never
    wasted."""
    from repro.models import vig
    from repro.models.module import init_params
    from repro.serve.engine import VigServeEngine

    cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
        image_size=32, embed_dims=(16,), depths=(1,), num_classes=3, k=3,
    )
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    eng = VigServeEngine(cfg, params, batch=2,
                         tuner_path=tmp_path / "tune.json")
    eng.warmup()
    assert eng.schedule is not None
    traced = []
    forward = vig.vig_forward

    def spy(*args, **kw):
        traced.append(kw["digc_impl"])
        return forward(*args, **kw)

    monkeypatch.setattr(vig, "vig_forward", spy)
    imgs = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    eng.infer(imgs)
    assert traced and all(c is eng.schedule for c in traced)


def test_vig_forward_with_cache_matches_without():
    """Threading a DigcState must not change blocked-tier results
    (exact path)."""
    from repro.models import vig
    from repro.models.module import init_params

    cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
        image_size=32, embed_dims=(16,), depths=(2,), num_classes=3, k=3
    )
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    imgs = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    st = vig.init_vig_state(cfg, 2, "blocked")
    out_nc = vig.vig_forward(params, imgs, cfg)
    out_c, st = vig.vig_forward(params, imgs, cfg, state=st)
    assert st.steps() == {"stage0": 2}
    np.testing.assert_allclose(
        np.asarray(out_nc), np.asarray(out_c), rtol=1e-5, atol=1e-5
    )
