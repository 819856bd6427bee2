"""Serving entry for ViG configurations: ``VigServeEngine`` driven
through ``submit``/``step`` on one thread, and the check of its answers.

The engine is built as a user builds it, with no option but the serving
size and ``autotune=False``: the tier, buckets, slots, guards and
admission stay at the program's defaults, so a change of default shows
in the benchmark. (The tuner measures candidate schedules on the first
tick and writes its choice into the checkout: with it on, set-up would
be long and could choose differently on the two sides of a check.)
"""

from __future__ import annotations

import numpy as np

from chipbench.references import vig as ref

# The served precision the configuration states (float32 at JAX's
# default matmul precision) and the control one step below it.
STATED = {"dtype": "float32", "precision": "default"}
CONTROL = {"dtype": "bfloat16", "precision": "default"}
WIDTH_KEYS = ("variant", "image_size", "patch", "in_chans", "embed_dims",
              "depths", "reduce_ratios", "k", "max_dilation", "use_dilation",
              "ffn_ratio", "num_classes")


def program_config(conf: dict):
    """The program's configuration for ``conf``, every width asserted
    equal to the file's."""
    from repro.models.vig import VIG_VARIANTS

    cfg = VIG_VARIANTS[conf["variant"]]
    want = dict(conf, variant=conf["architecture"])
    for key in WIDTH_KEYS:
        have = getattr(cfg, key)
        have = list(have) if isinstance(have, tuple) else have
        if have != want[key]:
            raise ValueError(f"{conf['name']}: the program's {key} is {have}, "
                             f"the configuration file says {want[key]}")
    if set(conf["num_knn"]) != {cfg.k}:
        raise ValueError(f"{conf['name']}: the program serves k = {cfg.k} in "
                         f"every block, the file says {conf['num_knn']}")
    return cfg


def weights(conf: dict, seed: int):
    """The seed's weights, made on the device in one call, in the tree
    the program reads (checked against the program's own shapes)."""
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


def seed_key(seed: int):
    """A threefry key holding 64 bits of the seed (``PRNGKey`` keeps 32)."""
    import jax

    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32))


class System:
    """One engine at one serving size, with its completions recorded:
    ``ticks`` holds each tick's served uids in lane order, ``logits``
    each served request's answer."""

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
        self.logits: dict[int, np.ndarray] = {}
        self.images: dict[int, np.ndarray] = {}

    def submit(self, uid: int, image: np.ndarray) -> None:
        from repro.serve.engine import VigRequest

        req = VigRequest(uid=uid, image=image)
        self.engine.submit(req)
        self._inflight.append(req)
        self.images[uid] = image

    def queued(self) -> int:
        return len(self.engine.queue)

    def step(self) -> list[tuple[int, bool]]:
        """One engine tick; the requests it completed, in lane order,
        each with whether it was answered."""
        self.engine.step()
        done = [r for r in self._inflight if r.done]
        if done:
            self._inflight = [r for r in self._inflight if not r.done]
        lanes = []
        out = []
        for r in done:
            ok = r.logits is not None and r.fault is None
            out.append((r.uid, ok))
            if ok:
                lanes.append(r.uid)
                self.logits[r.uid] = r.logits
        if lanes:
            self.ticks.append(tuple(lanes))
        return out

    def counters(self) -> dict:
        st = self.engine.stats()
        return {k: st[k] for k in ("live_lanes", "padded_lanes",
                                   "compiled_programs", "requests_failed",
                                   "quarantines", "fallback_level",
                                   "requests_served")}

    def capture(self, lanes: tuple[int, ...]):
        """Re-run one served tick (the same requests in the same lanes)
        through the cell's program with its DIGC calls captured: per
        lane, per block ``(h, y, idx)``, and the tick's width."""
        images = [self.images[u] for u in lanes]
        _, calls = self.engine.cell_graphs(images, self.size)
        per_lane = []
        for j in range(len(lanes)):
            per_lane.append([(nodes[j], nodes[j] if co is None else co[j],
                              idx[j]) for _, nodes, co, idx in calls])
        return per_lane, self.engine.bucket_for(len(lanes))

    def forget(self) -> None:
        """Drop what the warm-up recorded."""
        self.ticks.clear()
        self.logits.clear()
        self.images.clear()

    def close(self) -> None:
        self.engine = None


def check(system: System, seed: int, want: int,
          limits: dict) -> tuple[dict, int]:
    """The numbers of ``numbers`` over served ticks drawn from the seed,
    one of every batch width served and at least ``want`` requests, and
    how many requests that was.
    The ticks are re-run with their DIGC calls captured, then the
    engine is let go before the reference runs."""
    answers = []
    for lanes in sample_ticks(system.ticks, seed, want,
                              system.engine.bucket_for):
        per_lane, width = system.capture(lanes)
        answers.extend((system.images[u], system.logits[u], seen, width)
                       for u, seen in zip(lanes, per_lane))
    conf, size, w = system.conf, system.size, system.weights
    system.close()
    if not answers:
        return dict.fromkeys(NUMBERS, float("inf")), 0
    return numbers(conf, size, w, answers, limits), len(answers)


class Control:
    """The plain reference in the program's place, one step below the
    stated precision (bfloat16 throughout): answers and captures in the
    shapes ``System`` gives. Used to set the limits, never in a run."""

    def __init__(self, conf: dict, image_size: int, w):
        import jax.numpy as jnp

        self.w = w
        self.fwd = ref.make_forward(conf, image_size, dtype=jnp.bfloat16,
                                    precision=CONTROL["precision"])

    def answer(self, image):
        import jax.numpy as jnp

        logits, seen = self.fwd(self.w, jnp.asarray(image))
        f32 = [(np.asarray(h, np.float32), np.asarray(y, np.float32),
                np.asarray(i)) for h, y, i in seen]
        return np.asarray(logits, np.float32), f32


NUMBERS = ("list_gap", "logit_gap")


def numbers(conf: dict, image_size: int, w, answers, limits: dict) -> dict:
    """The numbers compared, over ``answers`` (a list of ``(image,
    served logits, per-block (h, y, idx), tick width)``):

    - ``list_gap``: the largest ``ref.row_gaps`` of any row of any
      block against that block's own features: DIGC at the stated k,
      dilation and pooling;
    - ``logit_gap``: the largest max |served - reference| / max
      |reference| of the logits, the reference run at the stated
      precision on the served lists where a row passed ``list_gap``'s
      limit and on its own exact lists where it did not: the stem,
      position embedding, MRConv, Grapher, FFN, downsamples, pooling
      and head, and what a wrong list does to them."""
    import jax.numpy as jnp

    taught = ref.make_forward(conf, image_size, precision=STATED["precision"],
                              taught=True)
    blocks = taught.blocks
    out = dict.fromkeys(NUMBERS, 0.0)
    for image, served, seen, width in answers:
        if len(seen) != len(blocks):
            return dict.fromkeys(NUMBERS, float("inf"))
        keep = []
        for blk, (h, y, idx) in zip(blocks, seen):
            if idx.shape != (blk.grid ** 2, blk.k) or y.shape[0] != blk.m:
                return dict.fromkeys(NUMBERS, float("inf"))
            gaps = np.asarray(ref.row_gaps(h, y, idx, blk.dilation))
            gaps = np.where(np.isfinite(gaps), gaps, np.inf)
            out["list_gap"] = max(out["list_gap"], float(gaps.max()))
            keep.append(gaps <= limits["list_gap"])
        want = taught(w, jnp.asarray(image),
                      [jnp.asarray(i) for _, _, i in seen], keep, rows=width)
        out["logit_gap"] = max(out["logit_gap"], _rel(served, want))
    return out


def _rel(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    gap = float(np.abs(got - want).max() / np.abs(want).max())
    return gap if np.isfinite(gap) else float("inf")


def sample_ticks(ticks: list[tuple[int, ...]], seed: int, want: int,
                 width=len):
    """Ticks drawn from the seed: first one of each ``width`` (the
    program's batch width of a tick's lane count) that was served, then
    more until they hold ``want`` requests."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 3]))
    order = [int(i) for i in rng.permutation(len(ticks))]
    picked, seen = [], set()
    for i in order:
        w = width(len(ticks[i]))
        if w not in seen:
            seen.add(w)
            picked.append(i)
    n = sum(len(ticks[i]) for i in picked)
    for i in order:
        if n >= want:
            break
        if i not in picked:
            picked.append(i)
            n += len(ticks[i])
    return [ticks[i] for i in picked]
