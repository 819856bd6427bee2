"""Mixture-of-Experts with explicit expert parallelism.

Distributed path (inside jit, via shard_map over the full mesh):
  * activations arrive batch-sharded over ("pod","data") and replicated
    over "model"; expert weights are sharded over "model" (E_loc = E /
    |model| experts per rank).
  * every rank routes its local tokens, gathers the ones destined for
    its *local* experts into fixed-capacity buffers (static shapes),
    runs the batched expert GEMMs, scatters weighted outputs back, and
    a psum over "model" combines expert contributions.
  * capacity cf=1.25: overflowing tokens are dropped (standard GShard
    semantics); the drop fraction is returned as a metric.

Single-device / no-mesh path: dense compute of all experts weighted by
the (zeroed) router probs — mathematically the capacity-unlimited
reference used by the tests.

Router is fp32; aux load-balance loss (Switch-style) is returned.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.module import active_mesh, spec


def moe_spec(cfg: ModelConfig):
    m = cfg.moe
    d, e, f = cfg.d_model, m.num_experts, m.d_expert
    s = {
        "router": spec((d, e), ("embed", None), init="fanin", dtype=jnp.float32),
        "w_gate": spec((e, d, f), ("experts", "embed", "expert_mlp")),
        "w_up": spec((e, d, f), ("experts", "embed", "expert_mlp")),
        "w_down": spec((e, f, d), ("experts", "expert_mlp", "embed")),
    }
    if m.num_shared:
        fs = m.d_expert * m.num_shared
        s["shared"] = {
            "wi_gate": spec((d, fs), ("embed", "mlp")),
            "wi_up": spec((d, fs), ("embed", "mlp")),
            "wo": spec((fs, d), ("mlp", "embed")),
        }
    return s


def _router(params, tokens, m):
    """tokens (T, D) -> (gates (T,k), sel (T,k), aux_loss, probs)."""
    logits = tokens.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, sel = lax.top_k(probs, m.top_k)
    gates = gates / jnp.sum(gates, -1, keepdims=True)
    # Switch load-balance loss: E * sum_e f_e * p_e
    e = probs.shape[-1]
    dispatch = jax.nn.one_hot(sel[:, 0], e)  # primary assignment
    f_e = jnp.mean(dispatch, axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f_e * p_e)
    return gates, sel, aux, probs


def _dense_moe(params, x, cfg: ModelConfig):
    """Reference path: every expert on every token (tiny configs only)."""
    m = cfg.moe
    dt = cfg.compute_dtype
    b, s, d = x.shape
    tokens = x.reshape(-1, d)
    gates, sel, aux, _ = _router(params, tokens, m)
    e = m.num_experts
    # combine weights (T, E): gate where selected else 0
    comb = jnp.zeros((tokens.shape[0], e), jnp.float32)
    comb = comb.at[jnp.arange(tokens.shape[0])[:, None], sel].add(gates)
    h_g = jnp.einsum("td,edf->tef", tokens, params["w_gate"].astype(dt))
    h_u = jnp.einsum("td,edf->tef", tokens, params["w_up"].astype(dt))
    h = jax.nn.silu(h_g) * h_u
    y_e = jnp.einsum("tef,efd->ted", h, params["w_down"].astype(dt))
    out = jnp.einsum("ted,te->td", y_e.astype(jnp.float32), comb).astype(dt)
    metrics = {"moe_aux": aux, "moe_drop_frac": jnp.float32(0.0)}
    return out.reshape(b, s, d), metrics


def _local_expert_moe(x_loc, router_w, w_gate, w_up, w_down, *, m, dt,
                      axis_name: str, n_shards: int):
    """shard_map body. x_loc (b_loc, s, d) replicated over `axis_name`;
    w_* are the local expert shards (E_loc, ...)."""
    b, s, d = x_loc.shape
    tokens = x_loc.reshape(-1, d)
    t = tokens.shape[0]
    e_loc = w_gate.shape[0]
    e = e_loc * n_shards
    rank = lax.axis_index(axis_name)
    e0 = rank * e_loc

    logits = tokens.astype(jnp.float32) @ router_w
    probs = jax.nn.softmax(logits, -1)
    gates, sel = lax.top_k(probs, m.top_k)
    gates = gates / jnp.sum(gates, -1, keepdims=True)
    dispatch1 = jax.nn.one_hot(sel[:, 0], e)
    aux = e * jnp.sum(jnp.mean(dispatch1, 0) * jnp.mean(probs, 0))

    cap = max(int(t * m.top_k / e * m.capacity_factor), 4)
    # local expert ids; out-of-range -> e_loc (overflow bucket)
    lid = sel - e0  # (T, k)
    in_range = (lid >= 0) & (lid < e_loc)
    lid_c = jnp.where(in_range, lid, 0)
    # position of each (t, j) within its expert, priority by token order
    onehot = jax.nn.one_hot(lid_c, e_loc, dtype=jnp.int32) * in_range[..., None]
    flat = onehot.reshape(t * m.top_k, e_loc)
    pos = jnp.cumsum(flat, axis=0) - flat  # entries before this one
    pos_sel = jnp.sum(pos * flat, axis=1).reshape(t, m.top_k)
    keep = in_range & (pos_sel < cap)
    dropped = jnp.sum(in_range & (pos_sel >= cap)).astype(jnp.float32)

    slot = jnp.where(keep, lid_c * cap + pos_sel, e_loc * cap)  # overflow row
    # dispatch: buffers (E_loc*cap + 1, d)
    tok_idx = jnp.broadcast_to(jnp.arange(t)[:, None], (t, m.top_k)).reshape(-1)
    buf = jnp.zeros((e_loc * cap + 1, d), dt)
    buf = buf.at[slot.reshape(-1)].add(tokens[tok_idx].astype(dt))
    buf = buf[: e_loc * cap].reshape(e_loc, cap, d)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(dt)))
    h = h * jnp.einsum("ecd,edf->ecf", buf, w_up.astype(dt))
    y = jnp.einsum("ecf,efd->ecd", h, w_down.astype(dt))
    y_flat = jnp.concatenate([y.reshape(e_loc * cap, d), jnp.zeros((1, d), dt)], 0)

    gathered = y_flat[slot.reshape(-1)].reshape(t, m.top_k, d)
    out = jnp.sum(gathered.astype(jnp.float32) * jnp.where(keep, gates, 0.0)[..., None], axis=1)
    out = lax.psum(out.astype(dt), axis_name)
    # aux identical on all ranks (same tokens); dropped differs -> psum
    dropped = lax.psum(dropped, axis_name) / jnp.float32(t * m.top_k)
    return out.reshape(b, s, d), aux, dropped


def moe_apply(params, x, cfg: ModelConfig, *, mesh=None, model_axis="model"):
    """Returns (out, metrics). Distributed iff a mesh with a >1 `model`
    axis is active."""
    m = cfg.moe
    dt = cfg.compute_dtype
    mesh = mesh or active_mesh()
    out_metrics = {}

    if mesh is not None and model_axis in mesh.axis_names and mesh.shape[model_axis] > 1:
        n_shards = mesh.shape[model_axis]
        assert m.num_experts % n_shards == 0, (m.num_experts, n_shards)
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        body = functools.partial(
            _local_expert_moe, m=m, dt=dt, axis_name=model_axis, n_shards=n_shards
        )
        mapped = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P(batch_axes or None, None, None),
                P(None, None),
                P(model_axis, None, None),
                P(model_axis, None, None),
                P(model_axis, None, None),
            ),
            out_specs=(P(batch_axes or None, None, None), P(), P()),
            check_vma=False,
        )
        out, aux, drop = mapped(
            x, params["router"], params["w_gate"], params["w_up"], params["w_down"]
        )
        # shard_map replicates aux across ranks; take as-is
        out_metrics = {"moe_aux": aux, "moe_drop_frac": drop}
    else:
        out, out_metrics = _dense_moe(params, x, cfg)

    if m.num_shared:
        sh = params["shared"]
        g = jnp.einsum("bsd,df->bsf", x, sh["wi_gate"].astype(dt))
        u = jnp.einsum("bsd,df->bsf", x, sh["wi_up"].astype(dt))
        out = out + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, sh["wo"].astype(dt))
    return out, out_metrics
