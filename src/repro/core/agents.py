"""Agent pools: the SoA agent state of the simulation.

BioDynaMo (§4.2) stores agents as heap objects behind a ResourceManager with a
custom pool allocator (§5.4.3) so attributes of nearby agents are packed densely.
On TPU the natural representation *is* structure-of-arrays: one fixed-capacity
array per attribute plus an ``alive`` mask.  malloc/free becomes masked
scatter/compaction, and the paper's "parallel agent add/remove" (§5.3.2) becomes
a deterministic prefix-sum compaction.

Capacity is static (XLA requires static shapes).  Overflow is recorded in
``overflow`` rather than raising, so the step function stays pure; the launcher
inspects it and re-shards with a larger capacity (our elastic-scaling path).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping

import jax
import jax.numpy as jnp

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class AgentPool:
    """Fixed-capacity structure-of-arrays agent container.

    Attributes
    ----------
    position:  (C, 3) float32 — agent centers in simulation space.
    diameter:  (C,)   float32 — agent geometry (spheres, §4.5.1).
    kind:      (C,)   int32   — agent type / state machine value (e.g. SIR state).
    age:       (C,)   float32 — iterations since creation (mortality models).
    alive:     (C,)   bool    — slot occupancy mask.
    static:    (C,)   bool    — §5.5 static-agent flag (force omission).
    attrs:     extensible per-model attribute arrays, all leading dim C.
    overflow:  ()     int32   — number of agents dropped due to capacity.
    """

    position: Array
    diameter: Array
    kind: Array
    age: Array
    alive: Array
    static: Array
    attrs: Dict[str, Array]
    overflow: Array

    # ---------------------------------------------------------------- helpers
    @property
    def capacity(self) -> int:
        return self.position.shape[0]

    def num_alive(self) -> Array:
        return jnp.sum(self.alive.astype(jnp.int32))

    def replace(self, **kw: Any) -> "AgentPool":
        return dataclasses.replace(self, **kw)

    def radius(self) -> Array:
        return 0.5 * self.diameter

    def get(self, name: str) -> Array:
        return self.attrs[name]

    def set_attr(self, name: str, value: Array) -> "AgentPool":
        attrs = dict(self.attrs)
        attrs[name] = value
        return self.replace(attrs=attrs)


def make_pool(
    capacity: int,
    position: Array,
    diameter: Array | float = 10.0,
    kind: Array | int = 0,
    attrs: Mapping[str, Array] | None = None,
    attr_defaults: Mapping[str, Any] | None = None,
) -> AgentPool:
    """Create a pool with the first ``n = len(position)`` slots alive.

    ``attrs`` supplies per-agent initial values of shape (n, ...); each is
    padded to capacity with zeros.  ``attr_defaults`` declares attribute
    names/dtypes that start at zero for all agents.
    """
    position = jnp.asarray(position, jnp.float32)
    n = position.shape[0]
    if n > capacity:
        raise ValueError(f"initial population {n} exceeds capacity {capacity}")
    pad = capacity - n

    def _pad(x: Array) -> Array:
        widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
        return jnp.pad(x, widths)

    pos = _pad(position)
    if jnp.ndim(diameter) == 0:
        diam = jnp.where(jnp.arange(capacity) < n, jnp.float32(diameter), 0.0)
    else:
        diam = _pad(jnp.asarray(diameter, jnp.float32))
    if jnp.ndim(kind) == 0:
        knd = jnp.full((capacity,), kind, jnp.int32)
    else:
        knd = _pad(jnp.asarray(kind, jnp.int32))
    alive = jnp.arange(capacity) < n

    full_attrs: Dict[str, Array] = {}
    for name, val in (attrs or {}).items():
        val = jnp.asarray(val)
        if val.shape[0] != n:
            raise ValueError(
                f"attr {name!r} has {val.shape[0]} rows, expected one per "
                f"initial agent ({n}); it is padded to capacity here"
            )
        full_attrs[name] = _pad(val)
    for name, proto in (attr_defaults or {}).items():
        if name in full_attrs:
            continue
        proto_arr = jnp.asarray(proto)
        full_attrs[name] = jnp.zeros((capacity,) + proto_arr.shape, proto_arr.dtype)

    return AgentPool(
        position=pos,
        diameter=diam,
        kind=knd,
        age=jnp.zeros((capacity,), jnp.float32),
        alive=alive,
        static=jnp.zeros((capacity,), bool),
        attrs=full_attrs,
        overflow=jnp.zeros((), jnp.int32),
    )


# --------------------------------------------------------------------------
# Attribute schema validation (the typed SoA attr surface of the model API).
# --------------------------------------------------------------------------

def canonicalize_attr(name: str, value: Any, n: int) -> Array:
    """Validate/broadcast one per-agent attribute to ``n`` leading rows.

    Scalars broadcast to ``(n,)`` (dtype inferred by jnp: python floats →
    f32, ints → i32, bools → bool) and strongly typed, as the step's output
    is: a weakly typed attribute would make the next jitted call of a
    chunked run trace again.  Arrays must already carry ``n`` rows.
    Used by :class:`~repro.core.api.Simulation` so a registration error
    surfaces at declaration time with the attribute's name, not as a shape
    mismatch deep inside ``make_pool``/jit.
    """
    arr = jnp.asarray(value)
    if arr.ndim == 0:
        return jnp.full((n,), arr, dtype=arr.dtype)
    if arr.shape[0] != n:
        raise ValueError(
            f"attr {name!r}: leading dim {arr.shape[0]} != {n} agents in this "
            f"group (per-agent attrs need one row per agent; scalars broadcast)"
        )
    return arr


def attr_signature(arr: Array) -> tuple:
    """The schema key of one attribute array: (trailing shape, dtype)."""
    return (tuple(arr.shape[1:]), jnp.dtype(arr.dtype))


def check_attr_schema(name: str, arr: Array, schema: Mapping[str, tuple]) -> None:
    """Assert ``arr`` matches the (trailing-shape, dtype) signature already
    registered for ``name``; raises with both signatures spelled out."""
    want = schema[name]
    got = attr_signature(arr)
    if got != want:
        raise TypeError(
            f"attr {name!r}: group declares trailing shape {got[0]} dtype "
            f"{got[1]}, but an earlier group declared {want[0]} {want[1]} — "
            f"all agent groups must share one SoA schema"
        )


# --------------------------------------------------------------------------
# Parallel add / remove (§5.3.2).
# --------------------------------------------------------------------------

def compact_indices(mask: Array, capacity: int, fill: int = 0):
    """Sort-free deterministic compaction of set-bit indices (§5.3.2).

    Returns ``(ids, valid, n)``: ``ids (capacity,) int32`` holds the indices
    of set bits in ascending index order (``ids[r]`` = r-th set index for
    ``r < min(n, capacity)``, ``fill`` elsewhere), ``valid (capacity,) bool``
    marks the occupied ranks, ``n ()`` is the total set-bit count (may exceed
    ``capacity`` — the caller accounts overflow).

    This replaces the stable-argsort compaction idiom (``argsort(~mask)[:k]``)
    with one prefix sum + one bounded scatter — O(C) work instead of an
    O(C log C) sort, and no (C,)-sized sorted permutation ever materializes.
    The migration / halo packing hot path runs this up to 10× per step, which
    made the packing sorts the distributed step's dominant non-force cost.
    """
    m = mask.shape[0]
    n = jnp.sum(mask.astype(jnp.int32))
    rank = jnp.cumsum(mask.astype(jnp.int32)) - 1           # rank among set bits
    slot = jnp.where(mask & (rank < capacity), rank, capacity)
    ids = (
        jnp.full((capacity,), fill, jnp.int32)
        .at[slot]
        .set(jnp.arange(m, dtype=jnp.int32), mode="drop")
    )
    valid = jnp.arange(capacity) < jnp.minimum(n, capacity)
    return ids, valid, n


def free_slot_table(alive: Array) -> Array:
    """``table[r]`` = index of the r-th free (dead) slot, capacity where none.

    Sort-free equivalent of ``jnp.sort(where(free, arange, C))``: ranks come
    from a prefix sum over the free mask, the table from one scatter.
    """
    c = alive.shape[0]
    ids, _, _ = compact_indices(~alive, c, fill=c)
    return ids


def remove_agents(pool: AgentPool, remove_mask: Array) -> AgentPool:
    """Remove agents by mask.  O(C), no data movement (mask clear only).

    The paper swaps removed agents with the vector tail to keep storage dense;
    on TPU the dense invariant is restored lazily by :func:`compact` (usually
    fused with the Morton sort, §5.4.2), so removal itself is free.
    """
    return pool.replace(alive=pool.alive & ~remove_mask)


def add_agents(
    pool: AgentPool,
    spawn_mask: Array,
    position: Array,
    diameter: Array,
    kind: Array,
    attrs: Mapping[str, Array] | None = None,
    age: Array | None = None,
) -> AgentPool:
    """Commit spawn requests into free slots (deterministic, parallel).

    ``spawn_mask`` is (C,) — typically "agent i divides this step"; the value
    arrays (``position`` etc.) are aligned with it (value at index i describes
    the child of agent i).  The k-th spawned agent (in index order) is placed
    in the k-th free slot.  Spawns beyond the free-slot count are dropped and
    counted in ``pool.overflow``.  Unspecified attrs are inherited from the
    spawning agent (BioDynaMo's copy-to-new event semantics, Fig 4.11).

    This is the §5.3.2 parallel-add: both rankings are prefix sums, the commit
    is a scatter — no locks, no atomics, deterministic under SPMD.
    """
    spawn_mask = spawn_mask & pool.alive
    c = pool.capacity
    free = ~pool.alive
    # Rank spawns and free slots (prefix sums; the free-slot table is the
    # sort-free scatter of free_slot_table — no O(C log C) sort).
    spawn_rank = jnp.cumsum(spawn_mask.astype(jnp.int32)) - 1          # (C,)
    n_free = jnp.sum(free.astype(jnp.int32))
    n_spawn = jnp.sum(spawn_mask.astype(jnp.int32))
    free_slots = free_slot_table(pool.alive)                           # ranks 0..

    fits = spawn_mask & (spawn_rank < n_free)
    # Scatter with drop-out-of-range semantics (index c is dropped).
    target = jnp.where(fits, free_slots[jnp.clip(spawn_rank, 0, c - 1)], c)
    new_alive = pool.alive.at[target].set(True, mode="drop")
    new_pos = pool.position.at[target].set(position, mode="drop")
    new_diam = pool.diameter.at[target].set(diameter, mode="drop")
    new_kind = pool.kind.at[target].set(kind, mode="drop")
    src_age = jnp.zeros((c,), jnp.float32) if age is None else age
    new_age = pool.age.at[target].set(src_age, mode="drop")
    new_static = pool.static.at[target].set(False, mode="drop")

    new_attrs = dict(pool.attrs)
    attrs = dict(attrs or {})
    for name, arr in pool.attrs.items():
        src = attrs[name] if name in attrs else arr  # inherit from spawner
        new_attrs[name] = arr.at[target].set(src, mode="drop")

    overflow = pool.overflow + jnp.maximum(n_spawn - n_free, 0)
    return pool.replace(
        position=new_pos,
        diameter=new_diam,
        kind=new_kind,
        age=new_age,
        alive=new_alive,
        static=new_static,
        attrs=new_attrs,
        overflow=overflow,
    )


def permute(pool: AgentPool, perm: Array) -> AgentPool:
    """Reorder all agent attributes by ``perm`` (used by the Morton sort)."""
    take = lambda x: jnp.take(x, perm, axis=0)
    return pool.replace(
        position=take(pool.position),
        diameter=take(pool.diameter),
        kind=take(pool.kind),
        age=take(pool.age),
        alive=take(pool.alive),
        static=take(pool.static),
        attrs={k: take(v) for k, v in pool.attrs.items()},
    )


def permute_to(pool: AgentPool, dest: Array) -> AgentPool:
    """Scatter agent ``i`` to slot ``dest[i]`` (``dest`` must be a permutation).

    The scatter form of :func:`permute`: ``permute_to(pool, dest)`` equals
    ``permute(pool, argsort(dest))`` without materializing the inverse.  The
    sort-free layout sort computes destinations directly (offset + rank), so
    this avoids the argsort that inverting would need.
    """
    scat = lambda x: jnp.zeros_like(x).at[dest].set(x)
    return pool.replace(
        position=scat(pool.position),
        diameter=scat(pool.diameter),
        kind=scat(pool.kind),
        age=scat(pool.age),
        alive=scat(pool.alive),
        static=scat(pool.static),
        attrs={k: scat(v) for k, v in pool.attrs.items()},
    )


def compact(pool: AgentPool) -> AgentPool:
    """Move alive agents to the front (stable).  Restores density after removal."""
    # Stable argsort on "dead" flag: alive (0) before dead (1).
    perm = jnp.argsort((~pool.alive).astype(jnp.int32), stable=True)
    return permute(pool, perm)
