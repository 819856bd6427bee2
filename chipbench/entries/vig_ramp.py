"""Serving entry for ViG configurations whose k varies over the blocks
(a ``num_knn`` schedule, as the official isotropic ViG-S and ViG-B
ramp it from 9 to 18): the engine, the check of its answers and the
control of ``entries/vig.py``, on a program that must serve the file's
per-block k.

On a program with no per-block k schedule (``VigConfig.num_knn``) the
configuration cannot be served as the file states it, and building the
system raises at once, naming what is missing.
"""

from __future__ import annotations

from chipbench.entries import vig as base
from chipbench.entries.vig import (  # noqa: F401  (the entry's interface)
    NUMBERS, Control, check, numbers, sample_ticks, seed_key)
from chipbench.references import vig as ref


def program_config(conf: dict):
    """The program's configuration for ``conf``: every width asserted
    equal to the file's, and each block's k, as the program plans it
    at the native size, equal to the file's ``num_knn``."""
    from repro.models.vig import VIG_VARIANTS, vig_stage_plans

    cfg = VIG_VARIANTS[conf["variant"]]
    if getattr(cfg, "num_knn", None) is None:
        raise ValueError(f"{conf['name']}: the program has no per-block k "
                         f"schedule (VigConfig.num_knn) for {cfg.name!r}; "
                         f"the file's num_knn is {conf['num_knn']}")
    want = dict(conf, variant=conf["architecture"])
    for key in base.WIDTH_KEYS:
        have = getattr(cfg, key)
        have = list(have) if isinstance(have, tuple) else have
        if have != want[key]:
            raise ValueError(f"{conf['name']}: the program's {key} is {have}, "
                             f"the configuration file says {want[key]}")
    ks = [k for plan in vig_stage_plans(cfg) for k in plan.ks]
    if ks != list(conf["num_knn"]):
        raise ValueError(f"{conf['name']}: the program's per-block k is "
                         f"{ks}, the file says {conf['num_knn']}")
    return cfg


def weights(conf: dict, seed: int):
    """The seed's weights, made on the device in one call, checked
    against the program's own parameter shapes."""
    import jax

    from repro.models.module import abstract_params
    from repro.models.vig import vig_param_spec

    w = ref.init_weights(conf, seed_key(seed))
    want = jax.tree_util.tree_map(lambda s: s.shape, abstract_params(
        vig_param_spec(program_config(conf))))
    have = jax.tree_util.tree_map(lambda a: a.shape, w)
    if want != have:
        raise ValueError(f"{conf['name']}: weight tree differs from the "
                         "program's parameter spec")
    return jax.block_until_ready(w)


class System(base.System):
    """``entries/vig.py``'s engine and its record of completions, built
    on this entry's program configuration."""

    def __init__(self, conf: dict, image_size: int, seed: int):
        from repro.serve.engine import VigServeEngine

        self.conf = conf
        self.size = image_size
        self.cfg = program_config(conf)
        self.weights = weights(conf, seed)
        self.engine = VigServeEngine(self.cfg, self.weights,
                                     image_sizes=(image_size,),
                                     autotune=False)
        self.slots = self.engine.slots
        self._inflight: list = []
        self.ticks: list[tuple[int, ...]] = []
        self.logits: dict = {}
        self.images: dict = {}
