"""The paper's Table I cycle model must reproduce exactly."""

import inspect

from repro.core import (
    FPGAConfig,
    digc_hbm_bytes,
    fpga_cycles,
    fpga_latency_ms,
    tpu_digc_estimate,
    vig_resolution_to_nodes,
)
from repro.core.perfmodel import engine_cost_estimate


def test_table1_vig_tiny():
    # ViG-Tiny: N=M=196, D=192, k=8 with the paper's static parallelism.
    cyc = fpga_cycles(196, 196, 192, 8)
    assert cyc == {"DCM": 4704, "LSM": 3920, "GMM": 4704, "NSM": 224}


def test_latency_positive_and_scales():
    t1 = fpga_latency_ms(196, 196, 192, 8)
    t2 = fpga_latency_ms(4 * 196, 4 * 196, 192, 8)
    assert 0 < t1 < t2


def test_streaming_traffic_beats_naive():
    n = m = vig_resolution_to_nodes(1024)  # 4096 nodes
    s = digc_hbm_bytes(n, m, 192, 16, block_n=512, streaming=True)
    naive = digc_hbm_bytes(n, m, 192, 16, block_n=512, streaming=False)
    assert naive / s > 5  # the paper's memory-traffic claim
    # bigger node blocks amortize co-node re-reads (fewer Y sweeps)
    s_small = digc_hbm_bytes(n, m, 192, 16, block_n=64, streaming=True)
    assert s_small > s

def test_resolution_to_nodes():
    assert vig_resolution_to_nodes(224, 16) == 196
    assert vig_resolution_to_nodes(2048, 16) == 128 * 128
    assert vig_resolution_to_nodes(2048, 16, reduction=2) == 64 * 64


def test_tpu_estimate_fields():
    est = tpu_digc_estimate(4096, 4096, 192, 9, 1)
    assert est["flops"] == 2 * 4096 * 4096 * 192
    assert est["bound"] in ("compute", "memory", "merge")
    assert est["traffic_saving"] > 1
    assert est["latency_s"] > 0


def test_tpu_prior_ranks_topk_lsm_over_select_at_vig_b_width():
    """On a v5e one lax.top_k per tile took 0.106 ms against select's
    2.132 ms at ViG-B's widest block (8 x 196 rows, M = 196, D = 640,
    k*d = 72): the TPU prior must rank them so, and default to the
    engine's own merge."""
    kw = dict(b=8, block_m=256, backend="tpu")
    topk = engine_cost_estimate(196, 196, 640, 72, merge="topk", **kw)
    select = engine_cost_estimate(196, 196, 640, 72, merge="select", **kw)
    assert 5 * topk["merge_s"] < select["merge_s"]
    default = inspect.signature(engine_cost_estimate).parameters["merge"]
    assert default.default == "topk"
