"""Functional DIGC state (DESIGN.md §7): the graph-construction state
that persists across layers and requests — cluster centroids for
k-means warm starts, co-node norms for a frozen gallery, cached graphs
— held as an explicit pytree that is threaded in-and-out of ``digc()``
(``digc(..., state=, state_key=) -> (idx, new_state)``), through
``vig_forward``, and through a single donated ``jax.jit`` in
``serve.VigServeEngine``, so warm starts work *inside* compiled serving
and the donated buffers update in place.

Layout: ``DigcState.entries`` maps a caller-chosen key (e.g. the model
stage name) to a ``DigcStateEntry``:

  * ``step``      — () int32 call counter. 0 means cold: builders gate
    their warm-start paths on ``step > 0`` via ``lax.cond``, so the
    pytree structure is identical on every call (a jit requirement) and
    validity is a *runtime* value, not a trace-time one.
  * ``centroids`` — (B, C, D) k-means centroids (the cluster tier's
    warm start), or None for builders without them.
  * ``sq_y``      — (B, M) co-node squared norms (the blocked tier's
    frozen-gallery hook), or None.
  * ``row_step``  — optional (B,) int32 **per-row** call counters for
    multi-tenant serving (DESIGN.md §9): when present, builders gate
    warm/cold *per batch row* instead of per entry, so a batch may mix
    a warm tenant (row carried from its previous request) with a cold
    one (row just reset on slot admission) without either leaking into
    the other. Absent (None) on single-tenant state: the scalar
    ``step`` gate applies to the whole batch, the PR-3 behavior.
  * ``graph_idx`` / ``graph_dist`` / ``graph_snap`` / ``graph_age`` —
    the stale-graph serving buffers (DESIGN.md §12): the cached
    (B, N, k) graph last built for this entry, the (B,) per-row feature
    statistic it was built from, and the (B,) staleness age in gated
    calls. Allocated together via ``state_entry(graph_shape=)``; the
    drift-gated reuse policies (``DigcSpec.reuse``) serve the cached
    graph when drift stays under ``drift_tau`` and the age under
    ``max_stale``, rebuilding otherwise.

Invalidation rules (who may reuse what):

  * The pytree *structure* is fixed at init time (``DigcState.init`` /
    ``models.vig.init_vig_state``); entries are never created on the
    fly — a builder given no entry for its key computes statelessly and
    the state passes through unchanged.
  * Entry shapes are part of the compiled program: a workload change
    (batch, cluster count, co-node count) requires re-init. Builders
    check shapes *statically* and fall back to a cold build on
    mismatch rather than reading stale-shaped state.
  * ``centroids`` are drift-tolerant (an approximate tier's init):
    reuse across layers of a stage and across requests is safe.
    ``sq_y`` must match the co-node *contents* exactly: an entry with
    ``sq_y`` asserts the gallery identified by its key is frozen — the
    caller must re-init the state when the gallery version changes.
  * Cached graphs invalidate through three independent guards: a
    *static* shape check (a workload change means the buffers never
    engage), the *runtime* drift gate (``graph_snap`` vs the current
    feature statistic), and the staleness bound (``graph_age`` vs
    ``max_stale``). Only ``digc()``'s reuse path writes them.
  * Row reuse is **per tenant** (multi-tenant serving): a state row may
    only warm-start requests of the tenant that wrote it. The serving
    engine enforces this with ``take_rows`` / ``put_rows`` /
    ``reset_rows`` — a slot reassigned to a new tenant has its rows
    reset (``row_step`` 0 ⇒ cold), and padding lanes of a bucketed
    batch are never scattered back, so they cannot clobber live rows.

Why donation matters: serving threads the same state pytree through
every request (`state -> forward -> new state -> forward -> ...`).
Donating the argument lets XLA write the new centroids into the old
buffers, so steady-state serving allocates nothing for DIGC state and
the update is a true in-place carry — the compiled analogue of the
paper's on-chip residency.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def _like_sharding(src, new):
    """Re-place ``new`` with ``src``'s NamedSharding (eager row ops on
    sharded entries must not silently collapse a device-resident buffer
    onto the default device — DESIGN.md §10 state placement).

    A no-op under tracing (jit propagates shardings itself), for
    unsharded arrays, and when the row op changed the partitioned
    dimension itself (a take/put only ever changes the *row* axis,
    which serving keeps unpartitioned)."""
    if isinstance(new, jax.core.Tracer) or isinstance(src, jax.core.Tracer):
        return new
    sharding = getattr(src, "sharding", None)
    if not isinstance(sharding, jax.sharding.NamedSharding):
        return new
    try:
        return jax.device_put(new, sharding)
    except (ValueError, TypeError):  # shape no longer placeable: keep
        return new


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DigcStateEntry:
    """Per-key functional construction state (see module docstring)."""

    step: jax.Array  # () int32; 0 = cold
    centroids: Optional[jax.Array] = None  # (B, C, D) | None
    sq_y: Optional[jax.Array] = None  # (B, M) | None
    row_step: Optional[jax.Array] = None  # (B,) int32 | None; 0 = cold row
    # -- stale-graph serving buffers (DESIGN.md §12) --------------------
    # The cached, versioned graph artifact the drift-gated reuse
    # policies serve (``DigcSpec.reuse``): the last built (idx, dist)
    # pair, the per-row feature statistic it was built from, and the
    # per-row staleness age (gated calls since the last rebuild).
    # Validity rides ``row_step``/``step`` like every other buffer: a
    # cold row's cached graph is never read.
    graph_idx: Optional[jax.Array] = None  # (B, N, k) int32 | None
    graph_dist: Optional[jax.Array] = None  # (B, N, k) f32 | None
    graph_snap: Optional[jax.Array] = None  # (B,) f32 drift snapshot | None
    graph_age: Optional[jax.Array] = None  # (B,) int32; 0 = just built

    @property
    def warm(self) -> jax.Array:
        """Traced bool: has this entry been written at least once?"""
        return self.step > 0

    @property
    def row_warm(self) -> Optional[jax.Array]:
        """Traced (B,) bool: which rows have been written at least once.
        None when the entry carries no per-row counters."""
        if self.row_step is None:
            return None
        return self.row_step > 0

    def bump(self, **updates) -> "DigcStateEntry":
        """Functional update: advance the call counter(s), replace
        fields. ``row_step`` (when present) advances for every row —
        the serving engine discards padding lanes on scatter, so only
        live rows' counters persist."""
        if self.row_step is not None and "row_step" not in updates:
            updates["row_step"] = self.row_step + 1
        return dataclasses.replace(self, step=self.step + 1, **updates)

    # -- per-slot row lifecycle (multi-tenant serving, DESIGN.md §9) ----

    def _row_fields(self):
        # Every per-row buffer: the take/put/reset lifecycle, the crc32
        # integrity fingerprints and the finiteness screen all iterate
        # this tuple, so the cached-graph buffers get the same coverage
        # as the warm-start buffers by construction (DESIGN.md §11/§12).
        return (
            "centroids", "sq_y", "row_step",
            "graph_idx", "graph_dist", "graph_snap", "graph_age",
        )

    def take_rows(self, rows) -> "DigcStateEntry":
        """Gather batch rows: entry over rows ``rows`` (any index array/
        sequence; repeats allowed — padding lanes replicate a live
        row). The scalar ``step`` is copied, not aliased: the taken
        entry is typically donated into a jit, and an aliased buffer
        would invalidate the source entry's counter on real backends."""
        return _take_entries({"": self}, rows)[""]

    def put_rows(self, src: "DigcStateEntry", rows) -> "DigcStateEntry":
        """Scatter ``src``'s leading rows back: row ``i`` of ``src``
        lands at ``rows[i]`` of self. ``src`` rows beyond ``len(rows)``
        (padding lanes) are dropped — they can never clobber live rows.
        The scalar ``step`` is taken from ``src`` (the served entry)."""
        return _put_entries({"": self}, {"": src}, rows)[""]

    def row_buffers(self) -> dict[str, jax.Array]:
        """The allocated per-row buffers, by field name."""
        return {f: getattr(self, f) for f in self._row_fields()
                if getattr(self, f) is not None}

    def reset_rows(self, rows) -> "DigcStateEntry":
        """Zero the given rows (cold: ``row_step`` 0 routes builders to
        their cold path; the zeroed buffers are never read as values).
        Called when a slot is reassigned to a new tenant, so warm state
        never leaks across tenants. One compiled call (``_zero_rows``)."""
        return dataclasses.replace(self, **_zeroed(self.row_buffers(), rows))


@jax.jit
def _zero_rows(buffers, mask):
    """Zero the rows ``mask`` marks in every buffer of ``buffers`` (any
    pytree of arrays with a leading row axis). The mask's length is fixed
    by the slot count, so one compile serves every set of rows reset.
    Not donated: a caller may still hold the state it resets."""
    def zero(v):
        m = mask[: v.shape[0]].reshape((v.shape[0],) + (1,) * (v.ndim - 1))
        return jnp.where(m, jnp.zeros((), v.dtype), v)

    return jax.tree_util.tree_map(zero, buffers)


@jax.jit
def _take_rows(buffers, steps, rows):
    """Rows ``rows`` of every buffer of ``buffers`` (any pytree of arrays
    with a leading row axis), and a copy of each scalar of ``steps``: the
    taken state is typically donated into a jit, and an aliased counter
    would invalidate the source's. One compile per number of rows."""
    return (jax.tree_util.tree_map(lambda v: v[rows], buffers),
            jax.tree_util.tree_map(lambda v: v + 0, steps))


@jax.jit
def _put_rows(dst, src, rows):
    """``dst`` with row ``rows[i]`` of each buffer set to row ``i`` of the
    matching buffer of ``src``; rows of ``src`` beyond ``len(rows)`` are
    dropped. Not donated: a caller may still read the state it writes
    (the engine's graph statistics compare old and new rows)."""
    n = rows.shape[0]
    return jax.tree_util.tree_map(
        lambda d, v: d.at[rows].set(v[:n].astype(d.dtype)), dst, src)


def _take_entries(entries: dict, rows) -> dict:
    """``DigcStateEntry.take_rows(rows)`` of every entry of ``entries``,
    in one ``_take_rows`` call, each buffer re-placed with its source's
    NamedSharding."""
    rows = np.asarray(rows, np.int32).reshape(-1)
    bufs = {k: e.row_buffers() for k, e in entries.items()}
    taken, steps = _take_rows(bufs, {k: e.step for k, e in entries.items()},
                              rows)
    taken = jax.tree_util.tree_map(_like_sharding, bufs, taken)
    return {k: dataclasses.replace(e, step=steps[k], **taken[k])
            for k, e in entries.items()}


def _put_entries(entries: dict, srcs: dict, rows) -> dict:
    """``entries[k].put_rows(srcs[k], rows)`` for every key, in one
    ``_put_rows`` call over the buffers both sides hold (``srcs`` may
    hold host rows, a parked copy); each entry's ``step`` is its
    source's."""
    rows = np.asarray(rows, np.int32).reshape(-1)
    dst, new = {}, {}
    for k, e in entries.items():
        both = [(f, getattr(e, f), getattr(srcs[k], f))
                for f in e._row_fields()]
        dst[k] = {f: d for f, d, v in both if d is not None and v is not None}
        new[k] = {f: v for f, d, v in both if d is not None and v is not None}
    put = jax.tree_util.tree_map(_like_sharding, dst, _put_rows(dst, new, rows))
    return {k: dataclasses.replace(e, step=jnp.asarray(srcs[k].step), **put[k])
            for k, e in entries.items()}


def _zeroed(buffers, rows):
    """``buffers`` with ``rows`` zeroed by one ``_zero_rows`` call, each
    re-placed with its input's NamedSharding (jit may hand a sharded
    buffer back under an equivalent spec that is not equal to it)."""
    lens = [v.shape[0] for v in jax.tree_util.tree_leaves(buffers)]
    mask = np.zeros(max(lens, default=0), bool)
    mask[np.asarray(rows, np.int64).reshape(-1)] = True
    return jax.tree_util.tree_map(_like_sharding, buffers,
                                  _zero_rows(buffers, mask))


# -- state-integrity guards (fault-tolerant serving, DESIGN.md §11) --------
#
# The serving engine trusts its slot rows because every write goes
# through the sanctioned lifecycle above. A bit flip (host memory, a
# buggy injector, a bad device) bypasses that lifecycle — so the engine
# keeps a cheap per-row fingerprint of every slot row, recomputed after
# each sanctioned write and checked before each read. These helpers are
# host-side by construction (they hash concrete bytes); calling them on
# tracers is an error the engine never commits.


def entry_row_fingerprint(entry: DigcStateEntry, row: int) -> int:
    """crc32 over one row's bytes across every per-row buffer.

    Cheap (a few KB per row), deterministic, and sensitive to any bit
    of ``centroids`` / ``sq_y`` / ``row_step`` — a mismatch against the
    token recorded at the last sanctioned write means the row was
    mutated outside the lifecycle and must be cold-reset.
    """
    h = 0
    for v in entry.row_buffers().values():
        h = zlib.crc32(np.ascontiguousarray(np.asarray(v[row])).tobytes(), h)
    return h


def entry_row_finite(entry: DigcStateEntry, row: int) -> bool:
    """True when every float buffer of ``row`` is finite. A NaN/Inf in
    a warm row poisons every later request of its tenant (warm starts
    feed it back) — the engine screens served rows each tick."""
    for v in entry.row_buffers().values():
        host = np.asarray(v[row])
        if np.issubdtype(host.dtype, np.floating) and not np.isfinite(host).all():
            return False
    return True


def prefetch_park_rows(host_rows):
    """Start the host->device upload of parked rows ahead of the tick
    that binds them (prefetched parking restore, DESIGN.md §14).

    ``host_rows`` is what ``VigServeEngine._park`` stored: a
    ``DigcState`` of single-row entries with numpy leaves (or a
    ``{size: DigcState}`` dict on the multi-resolution lattice). The
    structure is preserved exactly — only the numpy leaves move to
    device via ``jax.device_put`` (asynchronous on real accelerator
    backends), so ``put_rows``'s ``jnp.asarray`` at bind time finds the
    transfer already done (or in flight) instead of paying it on the
    tick's critical path. Purely a placement change: the device values
    are bit-identical to a bind-time upload, and the engine's §11
    integrity screens still run against whatever rows end up bound."""
    return jax.tree_util.tree_map(
        lambda v: jax.device_put(v) if isinstance(v, np.ndarray) else v,
        host_rows,
    )


def state_entry(
    *,
    centroids_shape: Optional[tuple[int, ...]] = None,
    sq_y_shape: Optional[tuple[int, ...]] = None,
    graph_shape: Optional[tuple[int, int, int]] = None,
    dtype=jnp.float32,
    rows: Optional[int] = None,
    mesh=None,
    axis_name: str = "data",
) -> DigcStateEntry:
    """A cold entry with zero-initialized buffers of the given shapes.

    The zeros are never *read* as values — ``step == 0`` routes every
    builder to its cold path — they only fix the pytree leaves so the
    first and the thousandth call share one compiled program.

    ``rows`` allocates (rows,) per-row counters (``row_step``) for
    multi-tenant serving: warm/cold becomes a per-batch-row value and
    the ``take_rows``/``put_rows``/``reset_rows`` lifecycle applies.

    ``mesh`` places the entry for sharded construction (DESIGN.md §10):
    ``sq_y`` — the ring tier's per-shard co-node norms — is partitioned
    along ``axis_name`` on its co-node dimension (each device owns the
    norm shard its ``shard_map`` body reads/writes), while the
    counters and centroids are replicated across the mesh (they are
    per-row values every device needs). Entries placed this way stay
    device-resident through the row lifecycle: ``take_rows`` /
    ``put_rows`` re-place their results with the source buffer's
    sharding, and ``reset_rows``'s compiled call keeps it.
    """
    graph_b = None if graph_shape is None else graph_shape[0]
    entry = DigcStateEntry(
        step=jnp.zeros((), jnp.int32),
        centroids=(
            None if centroids_shape is None
            else jnp.zeros(centroids_shape, dtype)
        ),
        sq_y=None if sq_y_shape is None else jnp.zeros(sq_y_shape, jnp.float32),
        row_step=None if rows is None else jnp.zeros((rows,), jnp.int32),
        # ``graph_shape`` (B, N, k) allocates the stale-graph buffers
        # (DESIGN.md §12): cached (idx, dist), the per-row drift
        # snapshot and the staleness age. Like every other buffer the
        # zeros are structure, not values — a cold row rebuilds.
        graph_idx=(
            None if graph_shape is None else jnp.zeros(graph_shape, jnp.int32)
        ),
        graph_dist=(
            None if graph_shape is None
            else jnp.zeros(graph_shape, jnp.float32)
        ),
        graph_snap=(
            None if graph_shape is None else jnp.zeros((graph_b,), jnp.float32)
        ),
        graph_age=(
            None if graph_shape is None else jnp.zeros((graph_b,), jnp.int32)
        ),
    )
    if mesh is None:
        return entry
    if axis_name not in mesh.shape:
        raise ValueError(
            f"state_entry placement axis {axis_name!r} is not an axis "
            f"of the mesh (axes: {tuple(mesh.shape)}); pass the mesh's "
            "co-node ring axis as axis_name="
        )
    from jax.sharding import NamedSharding, PartitionSpec

    def place(v, spec):
        return None if v is None else jax.device_put(
            v, NamedSharding(mesh, spec)
        )

    sq_spec = PartitionSpec(None, axis_name)
    if (
        entry.sq_y is not None
        and entry.sq_y.shape[-1] % mesh.shape[axis_name] != 0
    ):
        # A ragged co-node count still *works* sharded (the ring pads
        # internally) but cannot be device_put along the axis;
        # replicate — placement is a performance choice, never a
        # semantic one.
        sq_spec = PartitionSpec()
    return dataclasses.replace(
        entry,
        step=place(entry.step, PartitionSpec()),
        centroids=place(entry.centroids, PartitionSpec()),
        sq_y=place(entry.sq_y, sq_spec),
        row_step=place(entry.row_step, PartitionSpec()),
        # Cached graphs are per-row values every device reads whole
        # (the reuse gate selects per batch row, not per shard):
        # replicate, like the centroids.
        graph_idx=place(entry.graph_idx, PartitionSpec()),
        graph_dist=place(entry.graph_dist, PartitionSpec()),
        graph_snap=place(entry.graph_snap, PartitionSpec()),
        graph_age=place(entry.graph_age, PartitionSpec()),
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DigcState:
    """Keyed collection of ``DigcStateEntry`` — the value threaded
    through ``digc()`` / ``vig_forward`` / ``VigServeEngine``.

    Entry row buffers have one static N (node count), so the
    multi-resolution engine (DESIGN.md §13) keeps one ``DigcState``
    per N-bucket and keys the §9-§12 row lifecycle — take/put/reset
    rows, parking, quarantine, cached graphs — by (slot, N-bucket):
    a slot's 224-cell rows and 448-cell rows are independent carries
    of the same tenant."""

    entries: dict[str, DigcStateEntry]

    @classmethod
    def init(cls, entries: Optional[dict[str, DigcStateEntry]] = None):
        return cls(entries=dict(entries or {}))

    def get(self, key: Optional[str]) -> Optional[DigcStateEntry]:
        if key is None:
            return None
        return self.entries.get(key)

    def set(self, key: str, entry: DigcStateEntry) -> "DigcState":
        return DigcState(entries={**self.entries, key: entry})

    def steps(self) -> dict[str, int]:
        """Host-side view of the per-key call counters (concrete only)."""
        return {k: int(e.step) for k, e in self.entries.items()}

    def row_steps(self) -> dict[str, list[int]]:
        """Host-side view of per-row counters (keys carrying them)."""
        return {
            k: [int(v) for v in e.row_step]
            for k, e in self.entries.items() if e.row_step is not None
        }

    # -- per-slot row lifecycle (multi-tenant serving, DESIGN.md §9) ----

    def take_rows(self, rows) -> "DigcState":
        """Gather batch rows from every entry (slot rows -> bucket
        lanes; repeats allowed for padding lanes): one compiled call
        (``_take_rows``) over the whole state, per entry what
        ``DigcStateEntry.take_rows`` gives."""
        return DigcState(entries=_take_entries(self.entries, rows))

    def put_rows(self, src: "DigcState", rows) -> "DigcState":
        """Scatter ``src``'s leading rows into every entry at ``rows``
        (bucket lanes -> slot rows; src rows beyond ``len(rows)`` —
        padding lanes — are dropped): one compiled call (``_put_rows``)
        over the whole state, per entry what ``DigcStateEntry.put_rows``
        gives. ``src`` may hold host (numpy) rows, a parked copy."""
        return DigcState(entries=_put_entries(self.entries, src.entries,
                                              rows))

    def reset_rows(self, rows) -> "DigcState":
        """Cold-reset the given rows in every entry (slots reassigned to
        new tenants): one compiled call over the whole state, whatever
        the number of rows."""
        zeroed = _zeroed(
            {k: e.row_buffers() for k, e in self.entries.items()}, rows
        )
        return DigcState(entries={
            k: dataclasses.replace(e, **zeroed[k])
            for k, e in self.entries.items()
        })

    # -- integrity guards (fault-tolerant serving, DESIGN.md §11) -------

    def row_fingerprints(self, rows, to_host=np.asarray
                         ) -> dict[str, dict[int, int]]:
        """Per-entry integrity tokens for the given slot rows.

        Batched variant of ``entry_row_fingerprint``: each per-row
        buffer crosses to host ONCE per call, not once per row — the
        engine checks/refreshes several lanes per tick, and the
        device->host sync (not the crc) is the guard's real cost.
        ``to_host`` makes each of those copies (the serving engine
        passes its tracer's, which counts them)."""
        out: dict[str, dict[int, int]] = {}
        for k, e in self.entries.items():
            tokens = {int(r): 0 for r in rows}
            for v in e.row_buffers().values():
                host = np.ascontiguousarray(to_host(v))
                for r in tokens:
                    tokens[r] = zlib.crc32(host[r].tobytes(), tokens[r])
            out[k] = tokens
        return out

    def rows_finite(self, rows, to_host=np.asarray) -> dict[int, bool]:
        """Which of the given slot rows are finite across every entry
        (host-side, one transfer per buffer, made by ``to_host``; per-row
        semantics of ``entry_row_finite``)."""
        finite = {int(r): True for r in rows}
        for e in self.entries.values():
            for v in e.row_buffers().values():
                host = to_host(v)
                if not np.issubdtype(host.dtype, np.floating):
                    continue
                for r in finite:
                    if finite[r] and not np.isfinite(host[r]).all():
                        finite[r] = False
        return finite

    def __len__(self) -> int:
        return len(self.entries)
