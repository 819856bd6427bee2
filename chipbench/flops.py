"""Model FLOPs of one image through a ViG, from the configuration's
shapes alone (2 FLOPs per multiply-add).

Counted: the stem, each block's fc_in (D x D), fc_graph (2D x D), fc_out
(D x D), fc1 and fc2 (D x ffn_ratio*D each way), the DIGC distance
contraction 2*N*M*D, the downsamples between pyramid stages, and the
head. Not counted: top-k selection and the max-relative gather (no
FLOPs), norms, activations, pooling and padded lanes.
"""

from __future__ import annotations

from chipbench.references.vig import plan


def per_image(conf: dict, image_size: int) -> float:
    dims, f = conf["embed_dims"], conf["ffn_ratio"]
    p, c = conf["patch"], conf["in_chans"]
    blocks = plan(conf, image_size)
    n0 = blocks[0].grid ** 2
    total = 2.0 * n0 * (p * p * c) * dims[0]
    for blk in blocks:
        n, d = blk.grid ** 2, dims[blk.stage]
        total += 2.0 * n * d * d * (1 + 2 + 1 + 2 * f)
        total += 2.0 * n * blk.m * d
        last = blk.index + 1 == conf["depths"][blk.stage]
        if last and blk.stage + 1 < len(dims):
            total += 2.0 * n * d * dims[blk.stage + 1]
    return total + 2.0 * dims[-1] * conf["num_classes"]
