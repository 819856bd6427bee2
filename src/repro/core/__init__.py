# The paper's primary contribution: Dynamic Image Graph Construction
# (DIGC) as a composable JAX feature — reference / blocked-streaming /
# fused-Pallas / distributed-ring / cluster / axial implementations
# behind one GraphBuilder registry, plus the graph ops and the paper's
# analytical performance model. Everything is batched-first: (B, N, D)
# in, (B, N, k) out, with (N, D) promoted to B=1.

from repro.core.builder import (
    DEGRADATION_LADDER,
    DigcSpec,
    GraphBuilder,
    available_impls,
    degraded_spec,
    fallback_chain,
    get_builder,
    list_builders,
    register,
    resolve_spec,
)
from repro.core.faults import (
    SITES,
    FaultError,
    FaultInfo,
    FaultPlan,
)
from repro.core.digc import (
    BIG,
    digc,
    digc_blocked,
    digc_reference,
    dilate,
    merge_topk,
    pairwise_sq_dists,
)
from repro.core.engine import (
    MERGE_STRATEGIES,
    select_topkd,
    stream_topk,
)
from repro.core.packedkey import (
    INT_BIG,
    idx_bits_for,
    pack_keys,
    unpack_keys,
)
from repro.core.state import (
    DigcState,
    DigcStateEntry,
    entry_row_fingerprint,
    entry_row_finite,
    state_entry,
)
from repro.core.tuner import (
    DigcTuner,
    TileConfig,
    VigSchedule,
    autotune_spec,
    host_key,
    workload_key,
)
from repro.core.graph import (
    AGGREGATORS,
    degree_histogram,
    edge_list,
    grid_pos_bias,
    knn_gather,
    mean_aggregate,
    mr_aggregate,
    sum_aggregate,
)
from repro.core.perfmodel import (
    FPGAConfig,
    TPUConfig,
    digc_flops,
    digc_hbm_bytes,
    fpga_cycles,
    fpga_latency_ms,
    tpu_digc_estimate,
    vig_resolution_to_nodes,
)
