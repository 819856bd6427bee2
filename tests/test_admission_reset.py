"""Admission's batched cold reset (DESIGN.md §9): the slots a tick binds
cold are zeroed by one compiled ``DigcState.reset_rows`` call and
re-fingerprinted by one token refresh. Both must be bit-identical to
resetting the same slots one at a time, each by an eager per-field
scatter followed by its own refresh, and the compiled reset must compile
once however many slots a tick resets."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.state import DigcState, _zero_rows, state_entry
from repro.serve.engine import VigRequest, VigServeEngine
from repro.models import vig
from repro.models.module import init_params
from test_engine_tracer import REUSE, _images, _iso, _pyr


def _eager_reset_rows(state: DigcState, rows) -> DigcState:
    """The reset as it was: one slot at a time, one eager scatter per
    per-row field."""
    for r in rows:
        state = DigcState(entries={
            k: dataclasses.replace(e, **{
                f: v.at[jnp.asarray([r], jnp.int32)].set(
                    jnp.zeros((), v.dtype))
                for f, v in e.row_buffers().items()})
            for k, e in state.entries.items()
        })
    return state


class _PerSlotEngine(VigServeEngine):
    """The admission order before the batched reset: each cold slot is
    zeroed by the eager scatter and re-fingerprinted the moment it is
    bound. (The tick's batched call then repeats it on rows already
    zero, through the same eager path.)"""

    def _admit(self, tenant_key, used):
        cold = len(self.last_resets)
        slot = super()._admit(tenant_key, used)
        if len(self.last_resets) > cold:
            self._reset_rows_all([slot])
        return slot

    def _reset_rows_all(self, slots):
        for size, st in self._slot_states.items():
            self._slot_states[size] = _eager_reset_rows(st, slots)
        self._refresh_tokens(slots)


def _full_state(slots=5, seed=0) -> DigcState:
    """Every kind of per-row buffer, filled with distinct values."""
    st = DigcState.init({
        "warm": state_entry(centroids_shape=(slots, 3, 4),
                            sq_y_shape=(slots, 6), rows=slots),
        "graph": state_entry(graph_shape=(slots, 7, 3), rows=slots),
    })
    rng = np.random.default_rng(seed)

    def fill(v):
        if v.ndim == 0:
            return jnp.asarray(3, v.dtype)
        if jnp.issubdtype(v.dtype, jnp.integer):
            return jnp.asarray(rng.integers(1, 100, v.shape), v.dtype)
        return jnp.asarray(rng.standard_normal(v.shape), v.dtype)

    return jax.tree_util.tree_map(fill, st)


def _assert_states_equal(a: DigcState, b: DigcState) -> None:
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("rows", [[], [3], [0, 2, 4], [4, 1, 0, 3, 2]],
                         ids=["none", "one", "three", "all"])
def test_compiled_reset_matches_per_slot_scatter(rows):
    st = _full_state()
    fields = {f for e in st.entries.values() for f in e.row_buffers()}
    assert fields == {"centroids", "sq_y", "row_step", "graph_idx",
                      "graph_dist", "graph_snap", "graph_age"}
    got = st.reset_rows(rows)
    _assert_states_equal(got, _eager_reset_rows(st, rows))
    # the scalar counters are untouched; rows outside ``rows`` too
    assert got.steps() == st.steps()
    # one entry alone takes the same compiled path
    e = st.entries["graph"]
    _assert_states_equal(
        DigcState.init({"graph": e.reset_rows(rows)}),
        _eager_reset_rows(DigcState.init({"graph": e}), rows))


def _engines(cfg, impl):
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    return [cls(cfg, params, digc_impl=impl, autotune=False)
            for cls in (VigServeEngine, _PerSlotEngine)]


# Each tick's tenants: named tenants keep their slot (warm rows the reset
# must leave alone), anonymous lanes free theirs and are bound cold again
# on the next tick.
TICKS = [["A", "B", None, None], ["A", None, None, None],
         ["A", "B", None, None, None, None, None, None]]


@pytest.mark.parametrize("maker,impl", [(_iso, "blocked"), (_pyr, "blocked"),
                                        (_iso, REUSE)],
                         ids=["iso", "pyr", "iso_reuse"])
def test_admission_tick_matches_per_slot_reset(maker, impl):
    cfg = maker()
    batched, per_slot = _engines(cfg, impl)
    uid = 0
    for t, tenants in enumerate(TICKS):
        images = _images(cfg, len(tenants), t)
        served = []
        for eng in (batched, per_slot):
            reqs = [VigRequest(uid=uid + i, image=im, tenant=tn)
                    for i, (im, tn) in enumerate(zip(images, tenants))]
            for r in reqs:
                eng.submit(r)
            assert eng.step() == len(reqs)
            served.append(reqs)
        uid += len(tenants)
        for a, b in zip(*served):
            np.testing.assert_array_equal(a.logits, b.logits)
        assert batched.last_resets == per_slot.last_resets
        assert len(batched.last_resets) == tenants.count(None) + (
            2 if t == 0 else 0)
        _assert_states_equal(batched.slot_state(), per_slot.slot_state())
        assert batched._row_tokens == per_slot._row_tokens


def test_tick_compiles_the_reset_once():
    cfg = _iso()
    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    eng = VigServeEngine(cfg, params, digc_impl="blocked", autotune=False)
    eng.tracer.recording = True
    uid = 0

    def tick(n):
        nonlocal uid
        for im in _images(cfg, n, uid):
            eng.submit(VigRequest(uid=uid, image=im))
            uid += 1
        assert eng.step() == n

    tick(1)  # allocates the slot state: nothing to reset yet
    assert "reset_calls" not in eng.tracer.totals()["counters"]
    _zero_rows.clear_cache()
    resets = 0
    for calls, n in enumerate((1, 3, 8), start=1):
        tick(n)
        resets += n
        assert eng.last_resets == list(range(n))
        assert _zero_rows._cache_size() == 1
        counters = eng.tracer.totals()["counters"]
        assert (counters["reset_calls"], counters["reset_rows"]) == (
            calls, resets)
    # the reset is no bucket program: one per bucket served (1, 4, 8)
    assert eng.stats()["compiled_programs"] == 3
