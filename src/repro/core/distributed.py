"""TeraAgent: the distributed simulation engine (Chapter 6).

One simulation is spatially decomposed over the device mesh: every device
owns a box-shaped subdomain and the agents inside it (Fig 6.1).  Each
iteration requires two kinds of neighbor-device communication:

  1. **migration** — agents whose position left the local box move to the
     owning neighbor (full agent record);
  2. **aura / halo exchange** — read-only copies of agents within one
     interaction radius of a face, so local force/behavior evaluation sees
     the complete neighborhood (§6.2.1).

The paper identifies (2) as the scaling bottleneck and attacks it with a
tailored serialization mechanism (§6.2.2) and delta encoding (§6.2.3).  The
TPU adaptation (DESIGN.md §2):

  * MPI send/recv        → ``jax.lax.ppermute`` rings along mesh axes.  A
    two/three-phase exchange (x, then y including x-halos, then z including
    both) covers corner neighbors exactly as dimension-ordered routing does.
  * tailored serialization → *attribute subsetting*: the halo buffer carries
    only (position, diameter, kind) — the attributes remote force/behavior
    evaluation actually reads — never the full agent record.  SoA arrays are
    already contiguous, so "packing" is a fixed-capacity compaction gather.
  * delta encoding + zstd → quantized delta codec (`core.delta`): positions
    go on the wire as int16/int8 deltas against the receiver's reconstruction,
    with per-slot freshness bits handling occupancy changes.  Wire bytes for
    positions drop 2×/4×; correctness is bounded by the quantization step
    (tests/test_distributed.py checks physics parity vs. the single-node
    engine).

All static shapes: halo/migration buffers have fixed capacities and overflow
*counters* (never UB).  Coordinates are stored in the device-local frame so
the whole step is a single SPMD program; the global space is a torus (the
paper's §4.4.11 toroidal boundary).

Per-iteration dataflow (DESIGN.md §4 distributed adoption, §5 scheduler):

  * the step IS the single-node operation schedule (`core/schedule.py`):
    :func:`distributed_scheduler` takes ``Scheduler.default(ecfg)`` and
    composes distribution as ops — ``migrate``/``halo_exchange`` inserted as
    pre ops, ``env_build``/``boundary``/``diffusion`` replaced by the
    domain-decomposed variants.  Behaviors, forces, §5.5 static-flag
    detection, and age are literally the same Operation values the
    single-node engine runs (no second pipeline to drift);
  * the neighbor index is built ONCE over the halo-extended grid (halo agents
    land in its boundary cells); behaviors / forces share it through a lazy
    :class:`~repro.core.neighbors.NeighborContext` — the dense ``(C, 27M)``
    candidate tensor only exists if something actually reads it, so
    ``force_impl="fused"`` steps never touch it;
  * packing (``migrate`` / ``halo_exchange``) is sort-free: channel selection
    and free-slot insertion are cumsum-rank compaction scatters
    (`agents.compact_indices`), not stable argsorts over the pool — O(C) and
    no (C,) permutation tensors on the 10-channel/step hot path; the
    ghost-extended grid build is sort-free too (`kernels/cell_rank` tiled-
    histogram ranks), so with the frequency-gated §5.4.2 layout sort off
    the whole per-device step lowers with zero HLO sort ops (asserted by
    bench_dist_fused's ``fused_sort_off`` probe);
  * wire bytes are accounted per step into ``DistState.halo_payload_bytes`` /
    ``halo_baseline_bytes`` so the §6.2.3 compression ratio is observable
    (``halo_wire_stats``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import delta as dcodec
from . import diffusion as dgrid
from .agents import AgentPool, compact_indices, free_slot_table, make_pool, remove_agents
from .behaviors import StepContext
from .engine import EngineConfig, count_kinds
from .grid import GridSpec, build_index_arrays, cell_coords
from .neighbors import NeighborContext
from .schedule import (
    HealthReport,
    Operation,
    OpContext,
    Scheduler,
    apply_boundary,
    apply_force,
    empty_health,
    force_pass,
    seal,
)


def shard_map(f, mesh, in_specs, out_specs):
    # check_vma off: the varying-axes checker has no rule for pallas_call,
    # which the fused force path places inside the per-device step body.
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )

from jax.sharding import PartitionSpec as P

Array = jax.Array


# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DomainConfig:
    """Static spatial-decomposition description.

    mesh_axes:   mesh axis names decomposing space, in (x, y[, z]) order —
                 e.g. ``("data", "model")`` single-pod, ``("data", "model",
                 "pod")`` multi-pod (pod decomposes z).
    axis_sizes:  mesh extent along each of those axes.
    extent:      local subdomain edge length along each decomposed dim.
    depth:       edge length of non-decomposed dims (2D decomposition only).
    halo_width:  aura width == interaction radius.
    halo_capacity / migrate_capacity: per-direction buffer bounds.
    halo_codec:  "none" (f32 wire) | "int16" | "int8" (§6.2.3 delta codec).
    """

    mesh_axes: Tuple[str, ...]
    axis_sizes: Tuple[int, ...]
    extent: float
    halo_width: float
    halo_capacity: int
    migrate_capacity: int
    depth: float = 0.0
    halo_codec: str = "int16"
    # Overlap the halo collective with interior compute (DESIGN.md §4):
    # the distributed schedule splits the force op into an interior pass
    # over a local-only index (no ghost reads — data-independent of the
    # exchange, so XLA may run the collective concurrently) and a
    # boundary-shell pass over the ghost-extended index.  Bit-exact vs the
    # serial schedule; opt-in because it costs a second (local) grid build.
    overlap_halo: bool = False

    @property
    def n_decomposed(self) -> int:
        return len(self.mesh_axes)

    @property
    def n_devices(self) -> int:
        return int(np.prod(self.axis_sizes))

    def local_extent(self, dim: int) -> float:
        return self.extent if dim < self.n_decomposed else self.depth

    @property
    def codec_span(self) -> Tuple[float, float, float]:
        """Per-dim range a halo codec's full-scale payload must span: every
        local coordinate, ``[-halo, extent + halo]`` on decomposed dims and
        ``[0, depth]`` on the rest."""
        return tuple(
            self.extent + 2 * self.halo_width if d < self.n_decomposed
            else self.depth
            for d in range(3)
        )

    def ghost_capacity(self, pool_capacity: int) -> int:
        return pool_capacity + 2 * self.n_decomposed * self.halo_capacity

    def grid_spec(self, box_size: float, max_per_cell: int,
                  use_morton: bool = True, rank_impl: str = "xla") -> GridSpec:
        """Grid over the halo-extended local domain."""
        origin = []
        dims = []
        for d in range(3):
            lo = -self.halo_width if d < self.n_decomposed else 0.0
            hi = self.local_extent(d) + (
                self.halo_width if d < self.n_decomposed else 0.0
            )
            origin.append(lo)
            dims.append(max(int(math.ceil((hi - lo) / box_size)), 1))
        return GridSpec(
            origin=tuple(origin),
            box_size=box_size,
            dims=tuple(dims),
            max_per_cell=max_per_cell,
            use_morton=use_morton,
            rank_impl=rank_impl,
        )

    def device_coords(self, dev: int) -> Tuple[int, ...]:
        """Mesh coordinates of linear device index ``dev`` — the single
        definition of the x-major (mesh_axes-order) linearization shared by
        agent binning (:func:`init_dist_state`) and the model API's
        substance splitting (`Simulation.distribute`)."""
        coords = []
        for d in reversed(range(self.n_decomposed)):
            coords.append(dev % self.axis_sizes[d])
            dev //= self.axis_sizes[d]
        return tuple(coords[::-1])


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class HaloCodecState:
    """Per-device delta-codec state for all (dim, direction) halo channels.

    send_ref / recv_ref: (D, 2, H, 3) f32 — receiver reconstructions.
    prev_ids:            (D, 2, H) i32 — previous slot occupants (freshness).
    """

    send_ref: Array
    recv_ref: Array
    prev_ids: Array
    scale: Array  # (3,) f32 — per-dim quantization step

    @staticmethod
    def create(n_dims: int, capacity: int, scale) -> "HaloCodecState":
        return HaloCodecState(
            send_ref=jnp.zeros((n_dims, 2, capacity, 3), jnp.float32),
            recv_ref=jnp.zeros((n_dims, 2, capacity, 3), jnp.float32),
            prev_ids=jnp.full((n_dims, 2, capacity), -1, jnp.int32),
            scale=jnp.broadcast_to(jnp.asarray(scale, jnp.float32), (3,)),
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GhostFrame:
    """The double-buffered aura snapshot: the 2·D·H halo rows produced by
    the latest ``halo_exchange``, carried in :class:`DistState`.

    Contract (DESIGN.md §4, overlapped halo exchange): ``halo_exchange``
    *writes* the frame each step; the ghost-extended environment build
    *reads* it — under the overlapped schedule that read is the only
    consumer edge of the collective, so the interior force pass (which
    never touches the frame) is free of the collective in the dataflow
    graph, and XLA's input/output buffer aliasing ping-pongs the two
    physical copies across steps.  Rows are receiver-frame rebased, in
    (dim, direction) channel order after the C local pool rows."""

    position: Array  # (2·D·H, 3) f32
    radius: Array    # (2·D·H,)   f32
    kind: Array      # (2·D·H,)   i32
    alive: Array     # (2·D·H,)   bool

    @staticmethod
    def create(dcfg: "DomainConfig") -> "GhostFrame":
        n = 2 * dcfg.n_decomposed * dcfg.halo_capacity
        return GhostFrame(
            position=jnp.zeros((n, 3), jnp.float32),
            radius=jnp.zeros((n,), jnp.float32),
            kind=jnp.zeros((n,), jnp.int32),
            alive=jnp.zeros((n,), bool),
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DistState:
    """Per-device simulation state (stacked on a leading device axis).

    halo_payload_bytes / halo_baseline_bytes: cumulative per-device wire-byte
    account of ``halo_exchange`` (§6.2.2/§6.2.3 observability) — payload is
    what the codec actually ships, baseline the untruncated f32 full-attribute
    record.  i32 like the overflow counters; wraps after ~2 GiB of traffic
    (read and reset between epochs at scale).
    """

    pool: AgentPool
    grids: Dict[str, dgrid.DiffusionGrid]
    codec: HaloCodecState
    rng: Array                # (2,) uint32 key data
    step: Array               # () i32
    migrate_overflow: Array   # () i32
    halo_overflow: Array      # () i32
    halo_payload_bytes: Array   # () i32
    halo_baseline_bytes: Array  # () i32
    health: HealthReport      # per-device telemetry (DESIGN.md §7)
    ghost: GhostFrame         # latest aura snapshot (double buffer, §4)


# ---------------------------------------------------------------------------
# Packing helpers (the "tailored serialization", §6.2.2)
# ---------------------------------------------------------------------------


def _select(mask: Array, capacity: int) -> Tuple[Array, Array, Array]:
    """Deterministic compaction of up to ``capacity`` set indices.

    Sort-free: cumsum-rank + bounded scatter (`agents.compact_indices`)
    instead of a full stable argsort over the pool.  This runs once per
    (dim, direction) channel — up to 10× per step across ``migrate`` and
    ``halo_exchange`` — so the stable sorts it replaces dominated the
    packing cost at scale.  Invalid ranks point at index 0 (a real row;
    consumers mask with ``valid``).

    Returns (ids (cap,), valid (cap,), overflow ())."""
    ids, valid, n = compact_indices(mask, capacity)
    overflow = jnp.maximum(n - capacity, 0)
    return ids, valid, overflow


def _shift(x, axis_name: str, axis_size: int, direction: int):
    """ppermute ring shift: each device receives from its ``-direction``
    neighbor (direction=+1: data flows east/up along the ring)."""
    perm = [(i, (i + direction) % axis_size) for i in range(axis_size)]
    return jax.lax.ppermute(x, axis_name, perm)


# ---------------------------------------------------------------------------
# Migration (§6.2.1 repartitioning)
# ---------------------------------------------------------------------------


def _insert_records(pool: AgentPool, rec: Dict[str, Array], valid: Array) -> AgentPool:
    """Insert up to R received agent records into free pool slots."""
    c = pool.capacity
    r = valid.shape[0]
    free = ~pool.alive
    n_free = jnp.sum(free.astype(jnp.int32))
    free_slots = free_slot_table(pool.alive)   # sort-free rank → slot table
    rank = jnp.cumsum(valid.astype(jnp.int32)) - 1
    fits = valid & (rank < n_free)
    target = jnp.where(fits, free_slots[jnp.clip(rank, 0, c - 1)], c)

    pool = pool.replace(
        position=pool.position.at[target].set(rec["position"], mode="drop"),
        diameter=pool.diameter.at[target].set(rec["diameter"], mode="drop"),
        kind=pool.kind.at[target].set(rec["kind"], mode="drop"),
        age=pool.age.at[target].set(rec["age"], mode="drop"),
        alive=pool.alive.at[target].set(True, mode="drop"),
        static=pool.static.at[target].set(False, mode="drop"),
        attrs={
            k: v.at[target].set(rec["attrs"][k], mode="drop")
            for k, v in pool.attrs.items()
        },
        overflow=pool.overflow
        + jnp.maximum(jnp.sum(valid.astype(jnp.int32)) - n_free, 0),
    )
    return pool


def _pack_records(pool: AgentPool, ids: Array, valid: Array) -> Dict[str, Array]:
    take = lambda x: jnp.take(x, ids, axis=0)
    return dict(
        position=take(pool.position),
        diameter=jnp.where(valid, take(pool.diameter), 0.0),
        kind=jnp.where(valid, take(pool.kind), 0),
        age=jnp.where(valid, take(pool.age), 0.0),
        attrs={k: take(v) for k, v in pool.attrs.items()},
    )


def migrate(dcfg: DomainConfig, pool: AgentPool) -> Tuple[AgentPool, Array]:
    """Dimension-ordered migration of agents that left the local box."""
    overflow = jnp.zeros((), jnp.int32)
    for d in range(dcfg.n_decomposed):
        axis = dcfg.mesh_axes[d]
        size = dcfg.axis_sizes[d]
        ext = dcfg.extent
        coord = pool.position[:, d]
        east = pool.alive & (coord >= ext)
        west = pool.alive & (coord < 0.0)

        ids_e, val_e, ovf_e = _select(east, dcfg.migrate_capacity)
        ids_w, val_w, ovf_w = _select(west, dcfg.migrate_capacity)
        overflow = overflow + ovf_e + ovf_w

        rec_e = _pack_records(pool, ids_e, val_e)
        rec_w = _pack_records(pool, ids_w, val_w)
        # Rebase into the receiving device's frame (torus).
        rec_e["position"] = rec_e["position"].at[:, d].add(-ext)
        rec_w["position"] = rec_w["position"].at[:, d].add(ext)

        # Remove exactly the packed agents (invalid slots scatter out of range).
        c = pool.capacity
        sent_mask = jnp.zeros((c,), bool)
        sent_mask = sent_mask.at[jnp.where(val_e, ids_e, c)].set(True, mode="drop")
        sent_mask = sent_mask.at[jnp.where(val_w, ids_w, c)].set(True, mode="drop")
        pool = remove_agents(pool, sent_mask)

        # Ring exchange: east-bound records shift +1; west-bound shift −1.
        got_from_west = jax.tree.map(lambda x: _shift(x, axis, size, +1), rec_e)
        got_w_valid = _shift(val_e, axis, size, +1)
        got_from_east = jax.tree.map(lambda x: _shift(x, axis, size, -1), rec_w)
        got_e_valid = _shift(val_w, axis, size, -1)

        pool = _insert_records(pool, got_from_west, got_w_valid)
        pool = _insert_records(pool, got_from_east, got_e_valid)
    return pool, overflow


# ---------------------------------------------------------------------------
# Aura / halo exchange (§6.2.2 + §6.2.3)
# ---------------------------------------------------------------------------


def _slot_scales(
    dcfg: "DomainConfig", codec: HaloCodecState, fresh: Array, wire_dtype
) -> Array:
    """Two-scale coding: stale slots use the fine scale, fresh slots (new
    occupant, ref reset to 0) a coarse scale whose int range spans the whole
    halo-extended domain.  int16's fine scale already spans it, so only int8
    needs the coarse escape."""
    if jnp.dtype(wire_dtype) == jnp.dtype(jnp.int16):
        return codec.scale
    coarse = jnp.asarray(np.asarray(dcfg.codec_span) / 127.0, jnp.float32)
    fine = jnp.float32(dcfg.halo_width / 127.0)
    return jnp.where(fresh[:, None], coarse, fine)


def _codec_encode(
    dcfg: "DomainConfig",
    codec: HaloCodecState,
    d: int,
    s: int,
    pos: Array,
    ids: Array,
    wire_dtype,
) -> Tuple[Array, Array, HaloCodecState]:
    """Delta-encode one channel's positions; returns (payload, fresh, codec')."""
    fresh = ids != codec.prev_ids[d, s]
    ref = jnp.where(fresh[:, None], 0.0, codec.send_ref[d, s])
    ch = dcodec.DeltaCodec(ref=ref, scale=codec.scale)
    scale = _slot_scales(dcfg, codec, fresh, wire_dtype)
    q, ch = dcodec.encode(ch, pos, wire_dtype=wire_dtype, scale=scale)
    codec = dataclasses.replace(
        codec,
        send_ref=codec.send_ref.at[d, s].set(ch.ref),
        prev_ids=codec.prev_ids.at[d, s].set(ids),
    )
    return q, fresh, codec


def _codec_decode(
    dcfg: "DomainConfig",
    codec: HaloCodecState,
    d: int,
    s: int,
    q: Array,
    fresh: Array,
) -> Tuple[Array, HaloCodecState]:
    ref = jnp.where(fresh[:, None], 0.0, codec.recv_ref[d, s])
    ch = dcodec.DeltaCodec(ref=ref, scale=codec.scale)
    scale = _slot_scales(dcfg, codec, fresh, q.dtype)
    pos, ch = dcodec.decode(ch, q, scale=scale)
    codec = dataclasses.replace(codec, recv_ref=codec.recv_ref.at[d, s].set(ch.ref))
    return pos, codec


def halo_exchange(
    dcfg: DomainConfig,
    pool: AgentPool,
    codec: HaloCodecState,
) -> Tuple[Array, Array, Array, Array, HaloCodecState, Array, Dict[str, int]]:
    """Multi-phase aura exchange.

    Returns ghost-extended arrays ``(position, radius, kind, alive)`` whose
    first C rows are the local pool, followed by 2·D halo blocks, plus the
    updated codec state, overflow count, and a per-step wire-byte account.
    """
    c = pool.capacity
    h = dcfg.halo_capacity
    wire = {"payload_bytes": 0, "baseline_bytes": 0}
    wire_dtype = {"int16": jnp.int16, "int8": jnp.int8}.get(dcfg.halo_codec)
    bits = lambda n: (n + 7) // 8   # bitmask wire size, ceil (never 0 bytes)

    g_pos = pool.position
    g_rad = pool.radius()
    g_kind = pool.kind
    g_alive = pool.alive
    overflow = jnp.zeros((), jnp.int32)

    for d in range(dcfg.n_decomposed):
        axis = dcfg.mesh_axes[d]
        size = dcfg.axis_sizes[d]
        ext = dcfg.extent
        hw = dcfg.halo_width
        coord = g_pos[:, d]

        # Agents in each face band (includes halos of previous phases → corners).
        east_band = g_alive & (coord >= ext - hw) & (coord < ext)
        west_band = g_alive & (coord >= 0.0) & (coord < hw)

        packs = []
        for s, (band, sign) in enumerate(((east_band, +1), (west_band, -1))):
            ids, valid, ovf = _select(band, h)
            overflow = overflow + ovf
            pos = jnp.take(g_pos, ids, axis=0)
            # Rebase into receiver frame.
            pos = pos.at[:, d].add(-sign * ext)
            pos = jnp.where(valid[:, None], pos, 0.0)
            rad = jnp.where(valid, jnp.take(g_rad, ids), 0.0)
            knd = jnp.where(valid, jnp.take(g_kind, ids), 0).astype(jnp.int8)

            if wire_dtype is not None:
                slot_ids = jnp.where(valid, ids, -1)
                q, fresh, codec = _codec_encode(dcfg, codec, d, s, pos, slot_ids, wire_dtype)
                payload = dict(q=q, fresh=fresh, rad=rad, kind=knd, valid=valid)
                wire["payload_bytes"] += (
                    q.size * q.dtype.itemsize + bits(fresh.size) + rad.size * 4
                    + knd.size + bits(valid.size)
                )
            else:
                payload = dict(pos=pos, rad=rad, kind=knd, valid=valid)
                wire["payload_bytes"] += (
                    pos.size * 4 + rad.size * 4 + knd.size + bits(valid.size)
                )
            # Baseline = untruncated f32 full-attribute record (pos+rad+kind as f32/i32).
            wire["baseline_bytes"] += (
                pos.size * 4 + rad.size * 4 + knd.size * 4 + bits(valid.size)
            )
            packs.append((payload, sign))

        for s, (payload, sign) in enumerate(packs):
            got = jax.tree.map(lambda x: _shift(x, axis, size, sign), payload)
            if wire_dtype is not None:
                pos, codec = _codec_decode(dcfg, codec, d, s, got["q"], got["fresh"])
            else:
                pos = got["pos"]
            g_pos = jnp.concatenate([g_pos, pos], axis=0)
            g_rad = jnp.concatenate([g_rad, got["rad"]], axis=0)
            g_kind = jnp.concatenate([g_kind, got["kind"].astype(jnp.int32)], axis=0)
            g_alive = jnp.concatenate([g_alive, got["valid"]], axis=0)

    return g_pos, g_rad, g_kind, g_alive, codec, overflow, wire


# ---------------------------------------------------------------------------
# Distributed diffusion (1-voxel stencil halo along decomposed dims)
# ---------------------------------------------------------------------------


def _padding_mask(grid: dgrid.DiffusionGrid):
    """(nx, ny, nz) bool of *valid* voxels, or None when the grid carries no
    ghost-voxel padding (``n_valid`` unset — the even-split / single-node
    case).  Padded voxels sit beyond ``n_valid`` along each dim; they are
    outside the simulated domain and must stay ≡ 0 (zero-outside boundary),
    so diffusion masks them out of both the stencil input and the update."""
    if grid.n_valid is None:
        return None
    shape = grid.concentration.shape
    mask = jnp.ones(shape, bool)
    for d in range(3):
        bshape = [1, 1, 1]
        bshape[d] = shape[d]
        mask = mask & (
            jnp.arange(shape[d], dtype=jnp.int32) < grid.n_valid[d]
        ).reshape(bshape)
    return mask


def distributed_diffuse(
    dcfg: DomainConfig, grid: dgrid.DiffusionGrid, dt: float,
    boundary: str = "toroidal",
) -> dgrid.DiffusionGrid:
    """One Eq-4.3 step with the 1-voxel stencil halo exchanged over the mesh.

    ``boundary`` is the engine's §4.4.11 policy: "toroidal" keeps the ring
    wrap at the mesh edges (the global space is a device torus); any other
    value masks the wrapped face slices to zero at mesh-edge devices so the
    domain's outer faces see the single-node engine's zero-outside
    semantics instead of periodic-wrap concentrations.  Ghost-voxel padding
    (``grid.n_valid``, uneven substance splits) is masked out of the
    stencil and pinned to zero in the update.
    """
    u = grid.concentration
    mask = _padding_mask(grid)
    if mask is not None:
        u = jnp.where(mask, u, 0.0)
    padded = jnp.pad(u, 1)  # zero halo default (open boundary in z)
    for d in range(dcfg.n_decomposed):
        axis = dcfg.mesh_axes[d]
        size = dcfg.axis_sizes[d]
        lo_face = jax.lax.slice_in_dim(u, 0, 1, axis=d)
        hi_face = jax.lax.slice_in_dim(u, u.shape[d] - 1, u.shape[d], axis=d)
        from_west = _shift(hi_face, axis, size, +1)   # west neighbor's top slice
        from_east = _shift(lo_face, axis, size, -1)   # east neighbor's bottom
        if boundary != "toroidal":
            # Mesh-edge devices: the ring delivered the opposite edge's
            # face — the domain boundary is not periodic here, so the
            # outside concentration is 0 (matches the single-node engine).
            coord = jax.lax.axis_index(axis)
            from_west = jnp.where(coord == 0, 0.0, from_west)
            from_east = jnp.where(coord == size - 1, 0.0, from_east)
        # Place into padded halo positions (interior of the other dims).
        idx_lo = [slice(1, -1)] * 3
        idx_hi = [slice(1, -1)] * 3
        idx_lo[d] = slice(0, 1)
        idx_hi[d] = slice(padded.shape[d] - 1, padded.shape[d])
        padded = padded.at[tuple(idx_lo)].set(from_west)
        padded = padded.at[tuple(idx_hi)].set(from_east)

    lap = (
        padded[2:, 1:-1, 1:-1]
        + padded[:-2, 1:-1, 1:-1]
        + padded[1:-1, 2:, 1:-1]
        + padded[1:-1, :-2, 1:-1]
        + padded[1:-1, 1:-1, 2:]
        + padded[1:-1, 1:-1, :-2]
        - 6.0 * u
    ) / (grid.spacing**2)
    new = u * (1.0 - grid.decay_constant * dt) + grid.diffusion_coefficient * dt * lap
    if mask is not None:
        new = jnp.where(mask, new, 0.0)
    return dataclasses.replace(grid, concentration=new)


# ---------------------------------------------------------------------------
# The distributed step: the SAME scheduler, distribution expressed as ops
# (DESIGN.md §5; per-device body — wrap with shard_map below)
# ---------------------------------------------------------------------------


def _dist_fold_rng(state: DistState) -> Array:
    """DistState stores raw uint32 key data (shard_map-transparent)."""
    return jax.random.fold_in(
        jax.random.wrap_key_data(state.rng), state.step
    )


def migrate_op(dcfg: DomainConfig) -> Operation:
    """§6.2.1 repartitioning as a pre standalone op."""

    def fn(ctx: OpContext, state: DistState) -> DistState:
        pool, ovf = migrate(dcfg, state.pool)
        # Seal the migrated positions: the frame-rebase arithmetic
        # (``x ± extent``) is cheap enough for the backend to duplicate
        # into consumer fusions, where it may re-round differently per
        # program (serial vs overlapped schedules have different consumer
        # sets) — a 1-ulp wobble on migrated rows that breaks the
        # serial↔overlap bit-exactness contract.  ``seal`` pins every
        # rematerialized copy to one canonical rounding.
        pool = pool.replace(position=seal(pool.position))
        return dataclasses.replace(
            state, pool=pool, migrate_overflow=state.migrate_overflow + ovf
        )

    return Operation("migrate", fn, phase="pre")


def halo_exchange_op(dcfg: DomainConfig) -> Operation:
    """§6.2.2/§6.2.3 aura exchange as a pre standalone op.  Publishes the
    ghost-extended source arrays on the OpContext for the (replaced)
    ``env_build`` op, writes the halo rows into the state's
    :class:`GhostFrame` double buffer, and accounts wire bytes and overflow
    into the state."""

    def fn(ctx: OpContext, state: DistState) -> DistState:
        g_pos, g_rad, g_kind, g_alive, codec, ovf, wire = halo_exchange(
            dcfg, state.pool, state.codec
        )
        ctx.extras["halo_sources"] = (g_pos, g_rad, g_kind, g_alive)
        c = state.pool.capacity
        ghost = GhostFrame(
            position=g_pos[c:], radius=g_rad[c:],
            kind=g_kind[c:], alive=g_alive[c:],
        )
        return dataclasses.replace(
            state,
            codec=codec,
            ghost=ghost,
            halo_overflow=state.halo_overflow + ovf,
            halo_payload_bytes=state.halo_payload_bytes + wire["payload_bytes"],
            halo_baseline_bytes=state.halo_baseline_bytes + wire["baseline_bytes"],
        )

    return Operation("halo_exchange", fn, phase="pre")


def dist_env_build_op(dcfg: DomainConfig, ecfg: EngineConfig,
                      from_state_ghost: bool = False) -> Operation:
    """Environment build over the ghost-extended set; queries = local agents
    only.  The halo-extended GridIndex is built once and shared by behaviors,
    forces, and the fused cell-list kernel (DESIGN.md §4); the dense
    (C, 27M) candidate tensor is lazy — with candidate-free behaviors and
    ``force_impl="fused"`` it is never materialized.

    ``from_state_ghost`` (the overlapped schedule): read the halo rows from
    the state's :class:`GhostFrame` double buffer instead of the exchange
    op's trace-local ``halo_sources`` — the buffer read is then the only
    consumer edge of the collective, keeping the interior force pass off
    its dependency chain.  The reconstructed sources are value-identical:
    the first C rows are the pool at exchange time (nothing between the
    exchange and this op touches the pool)."""

    def fn(ctx: OpContext, state: DistState) -> DistState:
        if from_state_ghost:
            gf = state.ghost
            pool = state.pool
            g_pos = jnp.concatenate([pool.position, gf.position], axis=0)
            g_rad = jnp.concatenate([pool.radius(), gf.radius], axis=0)
            g_kind = jnp.concatenate([pool.kind, gf.kind], axis=0)
            g_alive = jnp.concatenate([pool.alive, gf.alive], axis=0)
        else:
            g_pos, g_rad, g_kind, g_alive = ctx.extras["halo_sources"]
        index = build_index_arrays(ecfg.spec, g_pos, g_alive)
        ctx.index = index
        ctx.neighbors = NeighborContext.for_sources(
            ecfg.spec, index, state.pool, g_pos, g_rad, g_kind, g_alive
        )
        ctx.pre_positions = state.pool.position
        ctx.sctx = StepContext(
            rng=ctx.rng,
            grids=dict(state.grids),
            neighbors=ctx.neighbors,
            dt=jnp.float32(ecfg.dt),
            step=ctx.step,
            min_bound=ecfg.min_bound,
            max_bound=ecfg.max_bound,
        )
        return state

    return Operation("env_build", fn, phase="pre")


# ---------------------------------------------------------------------------
# Interior / boundary-shell split (overlapped halo exchange, DESIGN.md §4)
# ---------------------------------------------------------------------------


def _interior_cell_tables(dcfg: DomainConfig, spec: GridSpec):
    """Static per-decomposed-dim bool tables over cell indices: True where
    the cell and both its ±1 neighbors along the dim are *ghost-free*.

    A cell can hold ghost rows iff its coordinate range reaches outside the
    owned band [0, extent) along some decomposed dim (live halo rows always
    carry at least one decomposed coordinate outside it).  A query row is
    *interior* iff no cell of its 27-box can hold a ghost — separable per
    dim, so the 27-box test is the AND of these 1-D tables.  Boundary
    comparisons lean inclusive (an exactly-face-aligned cell counts as
    ghost-capable): over-marking only grows the shell, never breaks the
    no-ghost-reads guarantee."""
    tables = []
    for d in range(dcfg.n_decomposed):
        n = spec.dims[d]
        box = spec.box_size
        lo = spec.origin[d]
        eps = 1e-6 * box
        ghost_capable = np.zeros((n,), bool)
        for i in range(n):
            c_lo = lo + i * box
            c_hi = lo + (i + 1) * box
            ghost_capable[i] = (c_lo < eps) or (c_hi > dcfg.extent - eps)
        ok = np.array([
            not ghost_capable[max(i - 1, 0): i + 2].any() for i in range(n)
        ])
        tables.append(jnp.asarray(ok))
    return tables


def interior_shell_masks(
    dcfg: DomainConfig, spec: GridSpec, position: Array, alive: Array
) -> Tuple[Array, Array]:
    """(interior, shell) row masks over the local pool — an exact partition
    of the live rows.  Membership comes from the same cell coordinates the
    grid build bins by, so the interior force pass walks exactly the cells
    the full pass would have walked for those rows — none of which can hold
    a ghost row."""
    coords = cell_coords(spec, position)  # (C, 3) int32, clipped to grid
    ok = jnp.ones(position.shape[:1], bool)
    for d, table in enumerate(_interior_cell_tables(dcfg, spec)):
        ok = ok & table[coords[:, d]]
    return alive & ok, alive & ~ok


def interior_env_build_op(dcfg: DomainConfig, ecfg: EngineConfig) -> Operation:
    """Local-only environment build for the overlapped schedule (pre op,
    scheduled *before* ``halo_exchange``): a grid index over the live pool
    alone — no ghost rows, hence no dependency on the collective — plus the
    interior/shell row masks.  Published on ``ctx.extras``; the
    ghost-extended build (op ``env_build``) still provides the step's
    canonical index / NeighborContext for behaviors, the shell pass, and
    §5.5 static detection."""

    def fn(ctx: OpContext, state: DistState) -> DistState:
        pool = state.pool
        index = build_index_arrays(ecfg.spec, pool.position, pool.alive)
        interior, shell = interior_shell_masks(
            dcfg, ecfg.spec, pool.position, pool.alive
        )
        ctx.extras["interior_index"] = index
        ctx.extras["interior_neighbors"] = NeighborContext.for_pool(
            ecfg.spec, index, pool
        )
        ctx.extras["interior_mask"] = interior
        ctx.extras["shell_mask"] = shell
        return state

    return Operation("interior_env_build", fn, phase="pre")


def interior_forces_op(dcfg: DomainConfig, ecfg: EngineConfig) -> Operation:
    """The interior half of the force op: the same ``mechanical_forces``
    dispatch (impl/tile/morton knobs included) over the *local-only* index
    and sources, row-masked to interior rows.  Reads nothing the collective
    produced, so XLA may schedule the halo exchange concurrently with it.
    Interior rows' 27-boxes hold no ghost-capable cell, and ghost rows never
    bin into non-ghost-capable cells, so per kept row the local cell lists
    match the ghost-extended ones slot for slot — the pass is bit-identical
    to the full pass restricted to those rows."""

    def fn(ctx: OpContext, state: DistState) -> DistState:
        ctx.extras["interior_force"] = force_pass(
            ecfg, ctx, state,
            index=ctx.extras["interior_index"],
            neighbors=ctx.extras["interior_neighbors"],
            row_mask=ctx.extras["interior_mask"],
        )
        return state

    return Operation("interior_forces", fn, phase="agent")


def shell_forces_op(dcfg: DomainConfig, ecfg: EngineConfig) -> Operation:
    """The boundary-shell half: the same dispatch over the ghost-extended
    index/context (``ctx.index`` / ``ctx.neighbors``), row-masked to shell
    rows, merged with the interior pass and applied as the displacement —
    ``where(interior, f_int, f_shell)`` selects exactly one pass per row,
    so the applied force equals the serial schedule's single full pass."""

    def fn(ctx: OpContext, state: DistState) -> DistState:
        shell_force = force_pass(
            ecfg, ctx, state,
            row_mask=ctx.extras["shell_mask"],
        )
        force = jnp.where(
            ctx.extras["interior_mask"][:, None],
            ctx.extras["interior_force"],
            shell_force,
        )
        pool = apply_force(state.pool, force, ecfg.dt)
        return dataclasses.replace(state, pool=pool)

    return Operation("shell_forces", fn, phase="agent")


def dist_boundary_op(dcfg: DomainConfig, ecfg: EngineConfig) -> Operation:
    """§4.4.11 boundary for the decomposed space: non-decomposed dims honor
    ``EngineConfig.boundary`` over [min_bound, max_bound] exactly like the
    single-node engine; decomposed dims are left free — they live on the
    device torus and migration repartitions them next iteration."""

    def fn(ctx: OpContext, state: DistState) -> DistState:
        pool = state.pool
        if dcfg.n_decomposed < 3:
            nd = apply_boundary(ecfg, pool.position[:, dcfg.n_decomposed:])
            pool = pool.replace(
                position=pool.position.at[:, dcfg.n_decomposed:].set(nd)
            )
        return dataclasses.replace(state, pool=pool)

    return Operation("boundary", fn, phase="post")


def dist_diffusion_op(dcfg: DomainConfig, ecfg: EngineConfig) -> Operation:
    """Eq 4.3 diffusion with the 1-voxel stencil halo exchange substituted
    for the single-node kernel (frequency semantics identical)."""

    def fn(ctx: OpContext, state: DistState) -> DistState:
        if not state.grids:
            return state
        grids = {
            name: distributed_diffuse(
                dcfg, g, ecfg.dt * max(ecfg.diffusion_frequency, 1),
                boundary=ecfg.boundary,
            )
            for name, g in state.grids.items()
        }
        return dataclasses.replace(state, grids=grids)

    return Operation(
        "diffusion", fn, phase="post",
        frequency=ecfg.diffusion_frequency, gate="cond",
    )


def distributed_scheduler(dcfg: DomainConfig, ecfg: EngineConfig) -> Scheduler:
    """The single-node default pipeline with distribution composed as ops:
    ``migrate`` + ``halo_exchange`` inserted after ``sort`` (pre phase), and
    ``env_build`` / ``boundary`` / ``diffusion`` replaced by their
    domain-decomposed variants.  Everything else — behaviors, the fused
    force dispatcher, §5.5 static-flag detection, age — is literally the
    same Operation the single-node engine runs, so the engines cannot drift.
    """
    sched = Scheduler.default(ecfg, fold_rng=_dist_fold_rng)
    sched = sched.insert_after("sort", migrate_op(dcfg))
    overlap = dcfg.overlap_halo and ecfg.force_params is not None
    if overlap:
        # Overlapped variant (DESIGN.md §4): the local-only build precedes
        # the exchange, the force op splits into an interior pass (no ghost
        # reads — off the collective's dependency chain) and a shell pass
        # that consumes the GhostFrame double buffer via env_build.  Op
        # order: sort → migrate → interior_env_build → halo_exchange →
        # env_build → behaviors → interior_forces → shell_forces → …
        # Bit-exact vs the serial branch below by construction.
        sched = sched.insert_after("migrate", interior_env_build_op(dcfg, ecfg))
        sched = sched.insert_after("interior_env_build", halo_exchange_op(dcfg))
        sched = sched.replace_op("forces", interior_forces_op(dcfg, ecfg))
        sched = sched.insert_after("interior_forces", shell_forces_op(dcfg, ecfg))
    else:
        sched = sched.insert_after("migrate", halo_exchange_op(dcfg))
    sched = sched.replace_op(
        "env_build", dist_env_build_op(dcfg, ecfg, from_state_ghost=overlap)
    )
    sched = sched.replace_op("boundary", dist_boundary_op(dcfg, ecfg))
    sched = sched.replace_op("diffusion", dist_diffusion_op(dcfg, ecfg))
    return sched


def distributed_step(
    dcfg: DomainConfig, ecfg: EngineConfig, state: DistState
) -> DistState:
    """One distributed iteration (the default distributed schedule)."""
    return distributed_scheduler(dcfg, ecfg).step(state)


# ---------------------------------------------------------------------------
# Host-side construction + shard_map wrapper
# ---------------------------------------------------------------------------


def init_dist_state(
    dcfg: DomainConfig,
    capacity: int,
    positions: np.ndarray,
    diameter: float | np.ndarray = 10.0,
    kind: Optional[np.ndarray] = None,
    grids: Optional[Dict[str, dgrid.DiffusionGrid]] = None,
    seed: int = 0,
    attrs: Optional[Dict[str, np.ndarray]] = None,
    stacked_grids: Optional[Dict[str, dgrid.DiffusionGrid]] = None,
) -> DistState:
    """Build the *stacked* global state from global agent positions (host).

    positions are global coordinates in [0, extent·axis_size) per decomposed
    dim; they are binned to devices and re-based to local frames.
    ``diameter`` and each ``attrs`` array may be scalar/per-agent — per-agent
    values are binned to devices alongside the positions.  ``grids`` are
    replicated to every device; ``stacked_grids`` (already carrying the
    leading device axis, e.g. the model API's domain-split substances) are
    used as-is and take precedence.
    """
    n_dev = dcfg.n_devices
    kind = np.zeros((positions.shape[0],), np.int32) if kind is None else kind
    diam_arr = None if np.ndim(diameter) == 0 else np.asarray(diameter, np.float32)
    attrs = {k: np.asarray(v) for k, v in (attrs or {}).items()}

    # Per-agent device coordinates; binning matches DomainConfig.device_coords
    # (the one definition of the device linearization) per mesh dim.
    dev_coord = []
    local = positions.copy().astype(np.float32)
    for d in range(dcfg.n_decomposed):
        c = np.floor(positions[:, d] / dcfg.extent).astype(np.int64)
        c = np.clip(c, 0, dcfg.axis_sizes[d] - 1)
        dev_coord.append(c)
        local[:, d] = positions[:, d] - c * dcfg.extent

    pools = []
    for dev in range(n_dev):
        coords = dcfg.device_coords(dev)
        sel = np.all(
            [dev_coord[d] == coords[d] for d in range(dcfg.n_decomposed)],
            axis=0,
        )
        n_here = int(sel.sum())
        if n_here > capacity:
            raise ValueError(
                f"device {dev} holds {n_here} agents > capacity {capacity}"
            )
        pools.append(
            make_pool(
                capacity,
                local[sel],
                diameter=diameter if diam_arr is None else jnp.asarray(diam_arr[sel]),
                kind=jnp.asarray(kind[sel]),
                attrs={k: jnp.asarray(v[sel]) for k, v in attrs.items()},
            )
        )
    pool = jax.tree.map(lambda *xs: jnp.stack(xs), *pools)

    base_grids = dict(grids or {})
    stacked_grids = dict(stacked_grids or {}) | {
        name: jax.tree.map(lambda x: jnp.stack([x] * n_dev), g)
        for name, g in base_grids.items()
        if name not in (stacked_grids or {})
    }
    scale = np.asarray(dcfg.codec_span, np.float32) / 32767.0
    codec = HaloCodecState.create(dcfg.n_decomposed, dcfg.halo_capacity, scale)
    codec = jax.tree.map(lambda x: jnp.stack([x] * n_dev), codec)

    # Raw uint32 key data (old-style PRNGKey) — passes through shard_map as a
    # plain array; wrapped with wrap_key_data inside the per-device body.
    rngs = jnp.stack([jax.random.PRNGKey(seed + i) for i in range(n_dev)])
    zeros = jnp.zeros((n_dev,), jnp.int32)
    return DistState(
        pool=pool,
        grids=stacked_grids,
        codec=codec,
        rng=rngs,
        step=zeros,
        migrate_overflow=zeros,
        halo_overflow=zeros,
        halo_payload_bytes=zeros,
        halo_baseline_bytes=zeros,
        health=jax.tree.map(lambda x: jnp.stack([x] * n_dev), empty_health()),
        ghost=jax.tree.map(
            lambda x: jnp.stack([x] * n_dev), GhostFrame.create(dcfg)
        ),
    )


def make_distributed_step(mesh, dcfg: DomainConfig, ecfg: EngineConfig,
                          scheduler: Optional[Scheduler] = None):
    """jit(shard_map(step)) over the stacked state representation.

    The global state stacks per-device states on a leading axis sharded over
    all spatial mesh axes (a single PartitionSpec prefix covers the whole
    pytree); inside shard_map each device sees a leading dim of one, squeezed
    before / restored after the per-device body.  ``scheduler`` overrides the
    default distributed schedule (custom ops; see :func:`distributed_scheduler`).
    """
    axes = tuple(dcfg.mesh_axes)
    spec_leading = P(axes)
    sched = scheduler or distributed_scheduler(dcfg, ecfg)

    def body(state: DistState) -> DistState:
        local = jax.tree.map(lambda x: x[0], state)
        idx = jnp.zeros((), jnp.int32)
        for i, ax in enumerate(axes):
            idx = idx * jnp.int32(dcfg.axis_sizes[i]) + jax.lax.axis_index(ax)
        local = dataclasses.replace(
            local,
            rng=jax.random.key_data(
                jax.random.fold_in(jax.random.wrap_key_data(local.rng), idx)
            ),
        )
        new = sched.step(local)
        new = dataclasses.replace(new, rng=state.rng[0])
        return jax.tree.map(lambda x: x[None], new)

    sharded = shard_map(body, mesh=mesh, in_specs=spec_leading, out_specs=spec_leading)
    return jax.jit(sharded)


def global_kind_counts(state: DistState, n_kinds: Optional[int] = None) -> Array:
    """Host-side observable across all devices.  Delegates to
    :func:`~repro.core.engine.count_kinds`, which flattens the device axis;
    ``n_kinds`` derives from the kinds present unless given — pass it
    explicitly when dynamics can reach kinds not yet present."""
    return count_kinds(state, n_kinds)


def halo_wire_stats(state: DistState) -> Dict[str, float]:
    """Host-side halo-traffic observable (§6.2.2/§6.2.3 compression account).

    Sums the per-device cumulative counters and reports the achieved
    compression ratio (baseline f32 full-record bytes / payload bytes
    actually shipped; 1.0 when nothing was sent yet).  ``wrapped`` flags an
    i32 counter overflow (~2 GiB of traffic on some device) — the ratio is
    garbage then; call :func:`reset_halo_wire_counters` between epochs.
    """
    # Host-side i64 sum: the per-device counters are i32, but the cross-
    # device total must not wrap at 2^31 (x64 is typically disabled in jax).
    payload = float(np.asarray(state.halo_payload_bytes, dtype=np.int64).sum())
    baseline = float(np.asarray(state.halo_baseline_bytes, dtype=np.int64).sum())
    wrapped = bool(
        np.any(np.asarray(state.halo_payload_bytes) < 0)
        | np.any(np.asarray(state.halo_baseline_bytes) < 0)
    )
    return {
        "payload_bytes": payload,
        "baseline_bytes": baseline,
        "compression_ratio": baseline / payload if payload > 0 else 1.0,
        "wrapped": wrapped,
    }


def reset_halo_wire_counters(state: DistState) -> DistState:
    """Zero the cumulative wire counters (read via :func:`halo_wire_stats`
    and reset between measurement epochs to stay clear of the i32 wrap)."""
    zeros = jnp.zeros_like(state.halo_payload_bytes)
    return dataclasses.replace(
        state, halo_payload_bytes=zeros, halo_baseline_bytes=zeros
    )


def make_packing_program(mesh, dcfg: DomainConfig):
    """jit-ed migrate + halo_exchange over the stacked state — the packing
    subgraph in isolation.  Shared by tests/benchmarks that assert it lowers
    with zero sort ops (see :func:`hlo_sort_count`); not part of the step.
    """
    axes = tuple(dcfg.mesh_axes)

    def body(state: DistState):
        local = jax.tree.map(lambda x: x[0], state)
        pool, mig_ovf = migrate(dcfg, local.pool)
        g_pos, g_rad, g_kind, g_alive, codec, halo_ovf, _ = halo_exchange(
            dcfg, pool, local.codec
        )
        out = (pool, g_pos, g_rad, g_kind, g_alive, codec, mig_ovf, halo_ovf)
        return jax.tree.map(lambda x: x[None], out)

    spec_leading = P(axes)
    return jax.jit(
        shard_map(body, mesh=mesh, in_specs=spec_leading, out_specs=spec_leading)
    )


def hlo_sort_count(lowered_text: str) -> int:
    """Count sort ops in lowered (StableHLO) or compiled (HLO) module text."""
    return lowered_text.count("stablehlo.sort") + lowered_text.count(" sort(")


def _parse_hlo_entry(text: str):
    """Entry-computation def-use graph of a compiled HLO module.

    Returns ``(operands, lines)``: per-instruction operand-name sets and the
    raw instruction lines.  Operand extraction skips a tuple-shaped result
    TYPE prefix (``name = (f32[...], ...) tuple(...)``) and reads only the
    first balanced paren group after the opcode — attributes like
    ``control-predecessors`` / ``sharding`` / ``metadata`` never count as
    data edges."""
    import re

    entry_lines: dict = {}
    cur_is_entry = False
    for line in text.splitlines():
        m = re.match(r"^(ENTRY )?%?[\w.\-]+\s*(\(.*\)\s*->.*)?{\s*$", line)
        if m:
            cur_is_entry = bool(m.group(1))
            continue
        if not cur_is_entry:
            continue
        s = line.strip()
        if s == "}":
            cur_is_entry = False
            continue
        im = re.match(r"^(ROOT )?%?([\w.\-]+) = ", s)
        if im:
            entry_lines[im.group(2)] = s

    names = set(entry_lines)
    operands = {}
    for n, s in entry_lines.items():
        rhs = s.split("=", 1)[1].lstrip()
        if rhs.startswith("("):  # tuple-shaped type prefix
            depth = 0
            for j, ch in enumerate(rhs):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        break
            rhs = rhs[j + 1:]
        i = rhs.find("(")
        depth = 0
        j = i
        for j in range(i, len(rhs)):
            if rhs[j] == "(":
                depth += 1
            elif rhs[j] == ")":
                depth -= 1
                if depth == 0:
                    break
        toks = set(re.findall(r"%?([\w.\-]+)", rhs[i + 1: j]))
        operands[n] = (toks & names) - {n}
    return operands, entry_lines


def hlo_overlap_report(compiled_text: str) -> dict:
    """Compile-only probe of the overlapped halo schedule (DESIGN.md §4).

    Each force pass lowers as a ``conditional`` (the :func:`force_pass`
    fusion fence) whose HLO metadata carries its scope (``forces`` /
    ``interior_forces`` / ``shell_forces``).  For every scope this walks the
    conditional's transitive *data* ancestors in the entry computation and
    counts ``collective-permute`` instructions, split by whether they carry
    the ``halo_exchange`` named-scope.  The overlap guarantee is structural:
    under ``overlap_halo`` the interior pass must have ZERO halo-scoped
    collective ancestors (XLA is free to run the exchange concurrently with
    it), while the shell pass — the positive control that the analysis sees
    dependencies at all — must have at least one.  Under the serial
    schedule the single ``forces`` pass depends on the exchange."""
    operands, lines = _parse_hlo_entry(compiled_text)

    def ancestors(seeds):
        seen, stack = set(), list(seeds)
        while stack:
            for o in operands.get(stack.pop(), ()):
                if o not in seen:
                    seen.add(o)
                    stack.append(o)
        return seen

    report = {
        "halo_collectives": sum(
            1 for s in lines.values()
            if "collective-permute" in s and "halo_exchange" in s
        ),
    }
    for scope in ("forces", "interior_forces", "shell_forces"):
        seeds = [
            n for n, s in lines.items()
            if " conditional(" in s and f"/{scope}/cond" in s
        ]
        anc = ancestors(seeds)
        coll = [n for n in anc if "collective-permute" in lines[n]]
        report[scope] = {
            "conditionals": len(seeds),
            "collective_ancestors": len(coll),
            "halo_collective_ancestors": len(
                [n for n in coll if "halo_exchange" in lines[n]]
            ),
        }
    return report
