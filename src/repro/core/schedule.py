"""Algorithm 8 as data: the operation scheduler (paper §4.4, DESIGN.md §5).

BioDynaMo's core modularity claim is that a simulation is a *schedule of
operations* — pre standalone ops, agent ops, post standalone ops, each with
an execution frequency — and that new functionality lands in a few lines of
code without touching the engine.  This module reifies that schedule:

  * :class:`Operation` — a named, pure ``(OpContext, state) -> state``
    transform with a declared *phase* (``pre`` / ``agent`` / ``post``), an
    execution *frequency* (§4.4.4 multi-scale support: fires on iterations
    where ``step % frequency == 0``; ``0`` disables the op statically), and
    a *gate* choosing how the frequency lowers (``"cond"`` → ``lax.cond``,
    skip the work entirely — right for expensive ops like sorting and
    diffusion; ``"mask"`` → predicated ``jnp.where`` select over the state —
    right for cheap ops on TPU where control flow costs more than compute).
    Both gates are bit-exact equivalents of each other.
  * :class:`Scheduler` — an immutable composition of operations plus the
    :class:`~repro.core.engine.EngineConfig` they were built from.  Execution
    order is the Algorithm-8 phase partition (all ``pre`` ops, then all
    ``agent`` ops, then all ``post`` ops), stable within each phase.
    ``insert_before`` / ``insert_after`` / ``replace_op`` / ``remove_op``
    derive new schedules without editing engine code.

Both engines run through one scheduler: ``engine.simulation_step`` is
``Scheduler.default(config).step``, and the distributed engine
(`core/distributed.py`) runs the *same* default pipeline with distribution
expressed as ops — ``migrate`` and ``halo_exchange`` inserted as pre ops and
the ``env_build`` / ``boundary`` / ``diffusion`` ops replaced by their
domain-decomposed variants.  Divergence between the two engines (the §5.5
static-flag gap, boundary/bounds drift) is impossible by construction:
there is no second pipeline to forget to update.

State duck-typing: an op receives whatever state dataclass flows through the
schedule — :class:`~repro.core.engine.SimulationState` single-node,
``DistState`` distributed.  The default ops only touch the fields both share
(``pool``, ``grids``, ``rng``, ``step``) via :func:`dataclasses.replace`;
distribution-only ops read the extra ``DistState`` fields.  Ops must
preserve the state's pytree structure (frequency gating routes both the
taken and untaken paths through the same ``lax.cond`` / ``where`` select).

Trace-time contract: :class:`OpContext` is a plain mutable object living
within one trace of the step function — the per-step scratch (grid index,
:class:`~repro.core.neighbors.NeighborContext`, the behaviors'
:class:`~repro.core.behaviors.StepContext`) that standalone ops publish and
agent ops consume.  Ops that *populate* the context (``env_build``) must run
at frequency 1: a frequency-gated op executes inside a ``lax.cond``
sub-trace, and context writes from there would leak tracers upward (the same
rule as ``NeighborContext.candidates(cache=False)``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import diffusion as dgrid
from .behaviors import StepContext
from .delta import seal
from .forces import mechanical_forces, update_static_flags_celllist
from .grid import build_index, sort_agents
from .neighbors import NeighborContext

Array = jax.Array

PHASES = ("pre", "agent", "post")
GATES = ("cond", "mask")


# ---------------------------------------------------------------------------
# Health telemetry (fault-tolerance detection layer, DESIGN.md §7)
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class HealthReport:
    """Saturation / corruption telemetry folded per step by the ``health``
    op and carried through the scan as part of the simulation state.

    Detection is pure and jit-safe (counters, never raises); *policy* runs
    host-side between run chunks — ``launch/elastic.check_abm_state`` turns
    a report into an :class:`~repro.launch.elastic.ElasticAction` (regrow
    capacity, halt on corruption).  All fields are () i32 per device:

    pool_overflow:       cumulative agents dropped by pool saturation
                         (``AgentPool.overflow`` — spawn commits and
                         migration inserts beyond free slots).
    migrate_overflow:    cumulative migration-buffer overflow (distributed;
                         0 single-node).
    halo_overflow:       cumulative halo-buffer overflow (distributed;
                         0 single-node).
    cell_overflow_steps: steps on which the neighbor grid had an over-full
                         cell (``GridIndex.overflowed``) — correctness is
                         kept by the fused path's dense fallback, but a
                         persistently over-full grid wants a larger
                         ``max_per_cell``.
    nonfinite_agents:    live agents with a non-finite position or float
                         attribute on the *latest* inspected step.
    nonfinite_steps:     cumulative steps with any non-finite live agent.
    """

    pool_overflow: Array
    migrate_overflow: Array
    halo_overflow: Array
    cell_overflow_steps: Array
    nonfinite_agents: Array
    nonfinite_steps: Array


def empty_health() -> HealthReport:
    zero = jnp.zeros((), jnp.int32)
    return HealthReport(
        pool_overflow=zero,
        migrate_overflow=zero,
        halo_overflow=zero,
        cell_overflow_steps=zero,
        nonfinite_agents=zero,
        nonfinite_steps=zero,
    )


# ---------------------------------------------------------------------------
# Operation protocol
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class OpContext:
    """Per-iteration scratch threaded through the ops of one step.

    Mutable and deliberately *not* a pytree: it is created and consumed
    within a single trace of the step function (like
    :class:`~repro.core.neighbors.NeighborContext`).  Standalone ops publish
    shared per-step artifacts here; later ops read them.

    config:        the EngineConfig the schedule was built from.
    step:          this iteration's counter (pre-increment).
    rng:           this iteration's folded PRNG key.
    index:         the GridIndex built by ``env_build``.
    neighbors:     the step's NeighborContext (lazy dense candidates).
    sctx:          the behaviors' StepContext (threads rng splits + grids).
    pre_positions: pool positions at environment-build time — the reference
                   for the §5.5 displacement test.
    extras:        free-form scratch for custom / distribution ops.
    """

    config: Any
    step: Array
    rng: Array
    index: Any = None
    neighbors: Optional[NeighborContext] = None
    sctx: Optional[StepContext] = None
    pre_positions: Optional[Array] = None
    extras: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class Operation:
    """One schedulable unit of Algorithm 8.

    fn:        pure ``(OpContext, state) -> state`` transform.
    phase:     "pre" | "agent" | "post" (Algorithm 8's three sections).
    frequency: fire on iterations where ``step % frequency == 0``; 1 = every
               iteration (ungated), 0 = statically disabled (§4.4.4).
    gate:      how a frequency > 1 lowers: "cond" (``lax.cond``, skip the
               work) or "mask" (predicated ``jnp.where`` state select).
    """

    name: str
    fn: Callable[[OpContext, Any], Any]
    phase: str = "agent"
    frequency: int = 1
    gate: str = "cond"

    def __post_init__(self):
        if self.phase not in PHASES:
            raise ValueError(f"unknown phase {self.phase!r}; expected {PHASES}")
        if self.gate not in GATES:
            raise ValueError(f"unknown gate {self.gate!r}; expected {GATES}")
        if self.frequency < 0:
            raise ValueError(f"frequency must be >= 0, got {self.frequency}")


def run_op(op: Operation, ctx: OpContext, state):
    """Execute one op with its frequency gate applied, under a named scope
    of the op's name: every instruction the op lowers to, gate included,
    carries the name in its ``op_name`` metadata, so a device trace
    attributes its time op by op."""
    if op.frequency == 0:
        return state
    with jax.named_scope(op.name):
        if op.frequency == 1:
            return op.fn(ctx, state)
        fires = (ctx.step % op.frequency) == 0
        if op.gate == "cond":
            return jax.lax.cond(
                fires, lambda s: op.fn(ctx, s), lambda s: s, state
            )
        new = op.fn(ctx, state)
        return jax.tree.map(lambda a, b: jnp.where(fires, a, b), new, state)


# ---------------------------------------------------------------------------
# The scheduler
# ---------------------------------------------------------------------------


def _fold_rng(state) -> Array:
    """Default per-step key derivation (single-node: state.rng is a key)."""
    return jax.random.fold_in(state.rng, state.step)


@dataclasses.dataclass(frozen=True)
class Scheduler:
    """An immutable operation schedule; ``step`` is the Algorithm-8 body.

    ``ops`` holds the operations in insertion order; execution partitions
    them by phase (pre → agent → post, stable within each phase), so an op
    inserted anywhere in the tuple still runs in its declared phase.
    ``fold_rng`` derives the per-step PRNG key from the state (the
    distributed engine overrides it: DistState carries raw key data).
    """

    config: Any
    ops: Tuple[Operation, ...]
    fold_rng: Callable[[Any], Array] = _fold_rng

    # -- construction -------------------------------------------------------

    @classmethod
    def default(cls, config, fold_rng: Callable[[Any], Array] = _fold_rng
                ) -> "Scheduler":
        """The paper's default pipeline from an EngineConfig: sort, env
        build, behaviors, mechanical forces, boundary, §5.5 static-flag
        update, diffusion, age.  Force-dependent ops are omitted when
        ``config.force_params`` is None (matching the engine's historical
        python-level gating)."""
        ops = [sort_op(config), env_build_op(config), behaviors_op(config)]
        if config.force_params is not None:
            ops.append(forces_op(config))
        ops.append(boundary_op(config))
        if config.force_params is not None:
            ops.append(static_flags_op(config))
        ops.append(diffusion_op(config))
        ops.append(age_op(config))
        ops.append(health_op(config))
        return cls(config=config, ops=tuple(ops), fold_rng=fold_rng)

    # -- execution ----------------------------------------------------------

    def ordered_ops(self) -> Tuple[Operation, ...]:
        """Execution order: the phase partition of ``ops``."""
        return tuple(
            op for phase in PHASES for op in self.ops if op.phase == phase
        )

    def step(self, state):
        """One iteration of Algorithm 8 over this schedule."""
        ctx = OpContext(
            config=self.config, step=state.step, rng=self.fold_rng(state)
        )
        for op in self.ordered_ops():
            state = run_op(op, ctx, state)
        return dataclasses.replace(state, step=state.step + 1)

    # -- composition --------------------------------------------------------

    def op_names(self) -> Tuple[str, ...]:
        return tuple(op.name for op in self.ops)

    def _index_of(self, name: str) -> int:
        names = self.op_names()
        if names.count(name) == 0:
            raise KeyError(f"no op named {name!r}; have {names}")
        if names.count(name) > 1:
            raise KeyError(f"ambiguous op name {name!r} in {names}")
        return names.index(name)

    def _check_new(self, op: Operation):
        if op.name in self.op_names():
            raise KeyError(f"op named {op.name!r} already scheduled")

    def insert_after(self, anchor: str, op: Operation) -> "Scheduler":
        self._check_new(op)
        i = self._index_of(anchor) + 1
        return dataclasses.replace(self, ops=self.ops[:i] + (op,) + self.ops[i:])

    def insert_before(self, anchor: str, op: Operation) -> "Scheduler":
        self._check_new(op)
        i = self._index_of(anchor)
        return dataclasses.replace(self, ops=self.ops[:i] + (op,) + self.ops[i:])

    def append(self, op: Operation) -> "Scheduler":
        self._check_new(op)
        return dataclasses.replace(self, ops=self.ops + (op,))

    def replace_op(self, name: str, op: Operation) -> "Scheduler":
        """Swap the op named ``name`` for ``op``, keeping its position."""
        i = self._index_of(name)
        if op.name != name:
            self._check_new(op)
        return dataclasses.replace(
            self, ops=self.ops[:i] + (op,) + self.ops[i + 1:]
        )

    def remove_op(self, name: str) -> "Scheduler":
        i = self._index_of(name)
        return dataclasses.replace(self, ops=self.ops[:i] + self.ops[i + 1:])


# ---------------------------------------------------------------------------
# Default operations (the Algorithm-8 pipeline as individual ops)
# ---------------------------------------------------------------------------


def apply_boundary(config, position: Array) -> Array:
    """§4.4.11 boundary policies over ``[min_bound, max_bound]``.

    Elementwise, so callers may pass any trailing slice of the position
    array (the distributed engine applies it to non-decomposed dims only).
    """
    lo, hi = config.min_bound, config.max_bound
    if config.boundary == "closed":
        return jnp.clip(position, lo, hi)
    if config.boundary == "toroidal":
        return lo + jnp.mod(position - lo, hi - lo)
    return position  # open


def sort_op(config) -> Operation:
    """§5.4.2 agent sorting at its configured frequency (pre standalone)."""

    def fn(ctx: OpContext, state):
        return dataclasses.replace(
            state,
            pool=sort_agents(config.spec, state.pool),
        )

    return Operation(
        "sort", fn, phase="pre", frequency=config.sort_frequency, gate="cond"
    )


def env_build_op(config) -> Operation:
    """Environment build (pre standalone): one GridIndex + lazy
    NeighborContext per iteration, published on the OpContext and shared by
    behaviors / forces / static detection (DESIGN.md §4).  Also snapshots
    the step-start positions for the §5.5 displacement test and constructs
    the behaviors' StepContext."""

    def fn(ctx: OpContext, state):
        # At sort_frequency=1 the layout sort ran immediately before this op
        # and nothing in between reorders the pool, so the build may assume a
        # layout-sorted pool and skip the cell_rank pass.  Single-node only:
        # the distributed engine replaces this op (migrate/halo run between
        # sort and its own build, breaking sortedness).
        index = build_index(
            config.spec,
            state.pool,
            assume_sorted=config.sort_frequency == 1,
        )
        ctx.index = index
        ctx.neighbors = NeighborContext.for_pool(config.spec, index, state.pool)
        ctx.pre_positions = state.pool.position
        ctx.sctx = StepContext(
            rng=ctx.rng,
            grids=dict(state.grids),
            neighbors=ctx.neighbors,
            dt=jnp.float32(config.dt),
            step=ctx.step,
            min_bound=config.min_bound,
            max_bound=config.max_bound,
        )
        return state

    return Operation("env_build", fn, phase="pre")


def behaviors_op(config) -> Operation:
    """The agent-op loop (Algorithm 8 L7–11): run every configured behavior,
    threading the StepContext (rng splits, secreted grids) between them."""

    def fn(ctx: OpContext, state):
        sctx, pool = ctx.sctx, state.pool
        for behavior in config.behaviors:
            sctx, pool = behavior(sctx, pool)
        ctx.sctx = sctx
        return dataclasses.replace(state, pool=pool, grids=dict(sctx.grids))

    return Operation("behaviors", fn, phase="agent")


def force_pass(config, ctx: OpContext, state, *, index=None, neighbors=None,
               row_mask=None) -> Array:
    """One ``mechanical_forces`` dispatch with the config's knobs applied.

    The single anchoring point for every force evaluation in either engine:
    the default ``forces`` op runs it once over the step's index/context;
    the distributed overlapped schedule runs it twice — an interior pass
    over a local-only index and a shell pass over the ghost-extended one —
    with complementary ``row_mask``s (DESIGN.md §4).  The calling op's
    scope (:func:`run_op`) names the pass in lowered-HLO op metadata, so the
    overlap report can locate each pass's fence (``/<op name>/cond``) and
    the halo collective in the scheduled module text.

    The dispatch runs inside a ``lax.cond`` on a *runtime* predicate
    (``any(alive)``) — a **fusion fence**.  XLA compiles a conditional
    branch as its own computation and fusion never crosses that boundary,
    so the per-row rounding of the force chain is fixed by the branch body
    alone, not by whatever program surrounds this pass.  Without the fence
    the same arithmetic embedded in the serial and overlapped distributed
    schedules fuses against different neighbor ops, and XLA:CPU's code
    generator may pick a different (equally IEEE-legal, per-program
    deterministic) evaluation for a handful of rows — a 1-ulp wobble that
    breaks the serial↔overlap bit-exactness guarantee.  The predicate must
    be runtime data (a constant ``True`` would fold and inline the
    branch); it is also semantically exact: with no live rows every force
    is zero.  The result still passes through :func:`seal` to pin one
    rounding on the merge/displacement consumers outside the fence.
    """
    pool = state.pool
    use_index = ctx.index if index is None else index
    use_neighbors = ctx.neighbors if neighbors is None else neighbors

    def _run(_):
        return mechanical_forces(
            config.spec,
            use_index,
            pool,
            config.force_params,
            active_capacity=config.active_capacity,
            impl=config.force_impl,
            neighbors=use_neighbors,
            fused_fallback=config.fused_overflow_fallback,
            tile=config.force_tile,
            tile_order=config.tile_order,
            morton_block=config.morton_block,
            morton_window=config.morton_window,
            morton_fallback=config.morton_window_fallback,
            row_mask=row_mask,
        )

    def _zero(_):
        return jnp.zeros((pool.capacity, 3), jnp.float32)

    force = jax.lax.cond(jnp.any(pool.alive), _run, _zero, None)
    return seal(force)


def apply_force(pool, force: Array, dt: float):
    """Apply ``position += force · dt`` with the product sealed by
    :func:`seal`.  The fence forbids the backend from contracting the
    multiply into the add (FMA): serial and overlapped distributed schedules
    apply the force through differently-shaped expressions, and per-program
    contraction choices put a 1-ulp wobble on the displacement — breaking
    the serial↔overlap bit-exactness contract.  With the product rounded
    separately the update is the same two IEEE ops in every schedule."""
    disp = seal(force * dt)
    return pool.replace(position=pool.position + disp)


def forces_op(config) -> Operation:
    """Mechanical forces (§4.5.1) + displacement (agent op).  Dispatches
    through the same ``mechanical_forces`` entry in both engines — the
    NeighborContext decides whether sources are the pool itself or the
    ghost-extended halo arrays (§6.2.1)."""

    def fn(ctx: OpContext, state):
        force = force_pass(config, ctx, state)
        pool = apply_force(state.pool, force, config.dt)
        return dataclasses.replace(state, pool=pool)

    return Operation("forces", fn, phase="agent")


def boundary_op(config) -> Operation:
    """§4.4.11 boundary condition (post standalone)."""

    def fn(ctx: OpContext, state):
        pool = state.pool
        pool = pool.replace(position=apply_boundary(config, pool.position))
        return dataclasses.replace(state, pool=pool)

    return Operation("boundary", fn, phase="post")


def static_flags_op(config) -> Operation:
    """§5.5 static-agent detection for the *next* iteration (post
    standalone).  Works unchanged over ghost-extended sources: live halo
    rows (whose per-step displacement is not locally known) are
    conservatively treated as moved — see
    :func:`~repro.core.forces.update_static_flags_celllist`."""

    def fn(ctx: OpContext, state):
        pool = state.pool
        nb = ctx.neighbors
        ghost_alive = None
        if nb.src_alive.shape[0] != pool.capacity:
            ghost_alive = nb.src_alive[pool.capacity:]
        displacement = pool.position - ctx.pre_positions
        pool = update_static_flags_celllist(
            config.spec,
            ctx.index,
            pool,
            displacement,
            config.force_params,
            query_position=nb.query_position,
            ghost_alive=ghost_alive,
        )
        return dataclasses.replace(state, pool=pool)

    return Operation("static_flags", fn, phase="post")


def diffusion_op(config) -> Operation:
    """Extracellular diffusion (Eq 4.3) at its frequency (post standalone).
    The effective dt is scaled by the frequency so skipped iterations are
    integrated on the firing one (§4.4.4)."""

    def fn(ctx: OpContext, state):
        if not state.grids:
            return state
        grids = {
            name: dgrid.diffuse(
                g,
                config.dt * max(config.diffusion_frequency, 1),
                impl=config.diffusion_impl,
            )
            for name, g in state.grids.items()
        }
        return dataclasses.replace(state, grids=grids)

    return Operation(
        "diffusion", fn, phase="post",
        frequency=config.diffusion_frequency, gate="cond",
    )


def age_op(config) -> Operation:
    """Advance the age of live agents (post standalone)."""

    def fn(ctx: OpContext, state):
        pool = state.pool
        pool = pool.replace(
            age=pool.age + jnp.where(pool.alive, config.dt, 0.0)
        )
        return dataclasses.replace(state, pool=pool)

    return Operation("age", fn, phase="post")


def health_op(config) -> Operation:
    """Fold saturation / corruption telemetry into ``state.health`` (last
    post standalone op — sees the fully updated step).

    Duck-typed over both engines: the pool/grid signals are shared; the
    distributed exchange counters (``migrate_overflow``/``halo_overflow``)
    are read when the state carries them and fold to 0 single-node.
    Detection is pure reductions (jit/scan/shard_map-safe, never raises);
    the host inspects ``state.health`` between chunks and reacts there
    (DESIGN.md §7).  ``EngineConfig.health_frequency`` gates it like any
    §4.4.4 frequency (0 disables statically)."""

    def fn(ctx: OpContext, state):
        pool = state.pool
        zero = jnp.zeros((), jnp.int32)
        bad = ~jnp.all(jnp.isfinite(pool.position), axis=-1)
        bad |= ~jnp.isfinite(pool.diameter) | ~jnp.isfinite(pool.age)
        for v in pool.attrs.values():
            if jnp.issubdtype(v.dtype, jnp.floating):
                bad |= ~jnp.all(
                    jnp.isfinite(v.reshape(v.shape[0], -1)), axis=-1
                )
        n_bad = jnp.sum((bad & pool.alive).astype(jnp.int32))
        cell_ovf = (
            ctx.index.overflowed.astype(jnp.int32)
            if ctx.index is not None else zero
        )
        prev = state.health
        report = HealthReport(
            pool_overflow=jnp.asarray(pool.overflow, jnp.int32),
            migrate_overflow=jnp.asarray(
                getattr(state, "migrate_overflow", zero), jnp.int32
            ),
            halo_overflow=jnp.asarray(
                getattr(state, "halo_overflow", zero), jnp.int32
            ),
            cell_overflow_steps=prev.cell_overflow_steps + cell_ovf,
            nonfinite_agents=n_bad,
            nonfinite_steps=prev.nonfinite_steps
            + (n_bad > 0).astype(jnp.int32),
        )
        return dataclasses.replace(state, health=report)

    return Operation(
        "health", fn, phase="post",
        frequency=config.health_frequency, gate="cond",
    )
