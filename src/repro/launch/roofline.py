"""Roofline terms from compiled dry-run artifacts.

    compute term    = per-chip HLO FLOPs / peak FLOP/s
    memory term     = per-chip HLO bytes accessed / HBM bandwidth
    collective term = per-chip collective bytes / ICI link bandwidth

cost_analysis() on the SPMD-partitioned executable reports *per-device*
flops / bytes (verified empirically), so the chips factor is already
applied. Collective bytes are parsed from the optimized HLO text
(collectives only exist post-partitioning): per op we take the result
shape bytes, x2 for all-reduce (ring reduce+broadcast), x(g-1)/g ring
efficiency where the replica group size g is parseable.

Peaks are the target chip's (``perfmodel.TARGET_PEAKS``, TPU v5e); the
collective term uses one ICI link's bandwidth.
"""

from __future__ import annotations

import re
from dataclasses import dataclass

from repro.core.perfmodel import TARGET_PEAKS

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s+(?:\()?([a-z0-9]+)\[([0-9,]*)\][^)]*?\s"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(",
)
_TUPLE_COLL_RE = re.compile(
    r"=\s+\(([^)]*)\)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(",
)
_GROUP_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collectives(hlo_text: str) -> dict:
    """Sum per-device collective traffic by op kind."""
    out = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0}
    counts = dict.fromkeys(out, 0)
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        shapes = []
        kind = None
        if m:
            kind = m.group(3)
            shapes = [(m.group(1), m.group(2))]
        else:
            mt = _TUPLE_COLL_RE.search(line)
            if mt:
                kind = mt.group(2)
                shapes = _SHAPE_RE.findall(mt.group(1))
        if not kind:
            continue
        nbytes = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        g = None
        mg = _GROUP_RE.search(line)
        if mg:
            g = int(mg.group(2))
        ring = (g - 1) / g if g and g > 1 else 1.0
        if kind == "all-reduce":
            nbytes = int(2 * nbytes * ring)
        elif kind in ("all-gather", "reduce-scatter"):
            nbytes = int(nbytes * ring)
        counts[kind] += 1
        out[kind] += nbytes
    return {"bytes_by_kind": out, "counts": counts,
            "total_bytes": sum(out.values())}


@dataclass
class Roofline:
    flops: float
    hbm_bytes: float
    collective_bytes: float
    compute_s: float
    memory_s: float
    collective_s: float
    bound: str

    def to_dict(self):
        return self.__dict__.copy()


def roofline_terms(flops: float, hbm_bytes: float,
                   collective_bytes: float) -> dict:
    """Seconds each term would take at the target chip's peaks."""
    return {
        "compute": flops / TARGET_PEAKS["bf16_flops"],
        "memory": hbm_bytes / TARGET_PEAKS["hbm_bw"],
        "collective": collective_bytes / TARGET_PEAKS["ici_link_bw"],
    }


def analyze(compiled, *, hlo_text=None) -> Roofline:
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = parse_collectives(text)
    terms = roofline_terms(flops, hbm, coll["total_bytes"])
    bound = max(terms, key=terms.get)
    return Roofline(
        flops=flops,
        hbm_bytes=hbm,
        collective_bytes=float(coll["total_bytes"]),
        compute_s=terms["compute"],
        memory_s=terms["memory"],
        collective_s=terms["collective"],
        bound=bound,
    )


def model_flops(cfg, kind: str, seq_len: int, global_batch: int,
                n_chips: int) -> float:
    """MODEL_FLOPS = 6*N*D (train) / 2*N*D (inference), N = params
    (active for MoE), D = tokens — per chip."""
    n_params = active_param_count(cfg)
    tokens = global_batch * (seq_len if kind != "decode" else 1)
    mult = 6 if kind == "train" else 2
    return mult * n_params * tokens / n_chips


def active_param_count(cfg) -> int:
    """Active (per-token) parameter count from the config."""
    d = cfg.d_model
    v = cfg.vocab_size
    emb = v * d * (1 if cfg.tie_embeddings else 2)
    if cfg.family == "ssm":
        s = cfg.ssm
        d_in = s.expand * d
        heads = d_in // s.head_dim
        conv_dim = d_in + 2 * s.n_groups * s.d_state
        per = d * (2 * d_in + 2 * s.n_groups * s.d_state + heads)
        per += s.d_conv * conv_dim + conv_dim + 3 * heads + d_in + d_in * d
        return emb + cfg.num_layers * per

    # attention
    dh = cfg.dh
    if cfg.mla:
        m = cfg.mla
        qk = m.qk_nope_dim + m.qk_rope_dim
        attn = d * cfg.num_heads * qk + d * m.kv_lora + d * m.qk_rope_dim
        attn += m.kv_lora * cfg.num_heads * (m.qk_nope_dim + m.v_dim)
        attn += cfg.num_heads * m.v_dim * d
    else:
        attn = d * dh * (cfg.num_heads + 2 * cfg.num_kv_heads) + cfg.num_heads * dh * d

    # channel mixer (active)
    if cfg.moe:
        mo = cfg.moe
        mlp = 3 * d * mo.d_expert * (mo.top_k + mo.num_shared)
    elif cfg.activation == "swiglu":
        mlp = 3 * d * cfg.d_ff
    else:
        mlp = 2 * d * cfg.d_ff

    if cfg.family == "hybrid":
        w = cfg.hybrid.lru_width or d
        rec = 2 * d * w + 2 * w * w + w * d + cfg.hybrid.d_conv * w
        pat = cfg.hybrid.pattern
        n_rec = sum(1 for p in pat if p == "rec")
        frac_rec = n_rec / len(pat)
        per = frac_rec * (rec + mlp) + (1 - frac_rec) * (attn + mlp)
        total = emb + cfg.num_layers * per
        return int(total)

    per = attn + mlp
    total = emb + cfg.num_layers * per
    if cfg.family == "audio":
        total += cfg.encdec.enc_layers * per + cfg.num_layers * attn  # cross-attn
    return int(total)
