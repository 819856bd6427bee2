"""GPipe-style pipeline parallelism over a `stage` mesh axis.

Layers (stacked along the leading dim) are split into S contiguous
stages; microbatches stream through the stage ring via
`collective_permute`. After M + S - 1 ticks every microbatch has
crossed every stage. Opt-in for deep dense models where FSDP+TP alone
leaves the HBM budget tight; the bubble fraction is (S-1)/(M+S-1).

The implementation is deliberately schedule-explicit (the tick loop is
`lax.fori_loop`, the handoff a single ppermute) so the collective
pattern in the lowered HLO is inspectable — this is what the dry-run
roofline reads.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    layer_fn: Callable,
    stacked_params,
    x: jax.Array,
    *,
    mesh: Mesh,
    num_microbatches: int,
    axis_name: str = "stage",
):
    """Run x (B, ...) through L stacked layers split over the `stage`
    axis. layer_fn(params_one_layer, activations) -> activations.

    Returns the final activations (B, ...), bit-equal to the sequential
    scan over all L layers (fp32; modulo dtype rounding otherwise).
    """
    n_stages = mesh.shape[axis_name]
    b = x.shape[0]
    assert b % num_microbatches == 0, (b, num_microbatches)
    mb = b // num_microbatches
    xs = x.reshape((num_microbatches, mb) + x.shape[1:])

    def stage_body(params_local, xs_local):
        # params arrive as the local stage shard (1, L/S, ...): drop the
        # sharded leading axis.
        params_local = jax.tree_util.tree_map(lambda p: p[0], params_local)
        stage = lax.axis_index(axis_name)
        ticks = num_microbatches + n_stages - 1
        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        state = jnp.zeros_like(xs_local[0])  # current activation
        outs = jnp.zeros_like(xs_local)

        def apply_stage(h):
            def scan_fn(carry, lp):
                return layer_fn(lp, carry), None

            out, _ = lax.scan(scan_fn, h, params_local)
            return out

        def tick(t, carry):
            state, outs = carry
            # stage 0 ingests microbatch t (when in range)
            mb_idx = jnp.clip(t, 0, num_microbatches - 1)
            fresh = lax.dynamic_index_in_dim(xs_local, mb_idx, keepdims=False)
            h = jnp.where(stage == 0, fresh, state)
            y = apply_stage(h)
            # last stage commits microbatch (t - (S-1)) when valid
            out_idx = jnp.clip(t - (n_stages - 1), 0, num_microbatches - 1)
            commit = (stage == n_stages - 1) & (t >= n_stages - 1)
            outs = lax.cond(
                commit,
                lambda o: lax.dynamic_update_index_in_dim(o, y, out_idx, 0),
                lambda o: o,
                outs,
            )
            state = lax.ppermute(y, axis_name, fwd)
            return state, outs

        _, outs = lax.fori_loop(0, ticks, tick, (state, outs))
        return outs[None]  # leading stage axis for out_specs

    mapped = jax.shard_map(
        stage_body,
        mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(axis_name),
        check_vma=False,
    )
    # params stacked (L, ...) -> sharded (S, L/S, ...) over stage axis
    def to_stages(p):
        l = p.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return p.reshape((n_stages, l // n_stages) + p.shape[1:])

    staged = jax.tree_util.tree_map(to_stages, stacked_params)
    outs = mapped(staged, xs)  # (S, M, mb, ...): only last stage's rows valid
    final = outs[-1]
    return final.reshape((b,) + x.shape[1:])
