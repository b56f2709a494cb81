"""The model API: one declarative description builds both engines (§4.4).

The paper's headline modularity claim (and BioDynaMo's, arXiv:2006.06775) is
that a complete model — agents, behaviors, substances, operations — is
declared in a few lines against one ``Simulation`` object, and the *same
model code* runs shared-memory or distributed (TeraAgent, arXiv:2509.24063).
This module is that surface for the TPU reproduction:

    sim = (Simulation(space=(0, 100), cell_size=10.0, boundary="closed")
           .add_agents(600, position=pos, diameter=5.0, kind=kinds,
                       exposure=0.0)
           .add_substance("attractant", diffusion=4.0, decay=0.002,
                          resolution=20)
           .use(secretion("attractant", 1.0), chemotaxis("attractant", 0.75))
           .mechanics(ForceParams())
           .observe("counts", my_counts_fn, frequency=4))
    final, obs = sim.run_jit(300)                     # laptop …
    final, obs = sim.distribute(mesh, dcfg).run(300)  # … or cluster

``build()`` compiles the description onto the *existing explicit layer* — it
returns the ``(EngineConfig, Scheduler, SimulationState)`` triple the
hand-wired pipeline uses, constructed through the very same primitives
(``spec_for_space``/``make_pool``/``Scheduler.default``/``init_state``), so
facade-built and hand-wired steps are bit-exact (tests/test_api.py) and the
explicit API remains the stable low-level layer.  Space bounds are stated
ONCE: the grid spec, the engine's boundary clamp, and every substance grid
derive from ``space``; the cell size derives from the declared interaction
radius (``cell_size``, defaulting to the largest agent diameter — the
contact-mechanics interaction radius).

Construction is host-side (concrete arrays): registration methods validate
shapes/dtypes eagerly so a model error surfaces with the attribute's name at
the declaration site, not as a shape mismatch inside jit.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import diffusion as dgrid
from .. import spans
from ..checkpoint import checkpoint as _ckpt
from .agents import (
    attr_signature,
    canonicalize_attr,
    check_attr_schema,
    make_pool,
)
from .behaviors import Behavior
from .engine import EngineConfig, SimulationState, init_state
from . import engine as _engine
from .forces import ForceParams
from .grid import spec_for_space
from .schedule import Operation, Scheduler

Array = jax.Array

# Pool fields that are not free-form attrs (have dedicated arguments).
_RESERVED_ATTRS = ("position", "diameter", "kind", "age", "alive", "static",
                   "overflow")


@dataclasses.dataclass(frozen=True)
class _AgentGroup:
    n: int
    position: Array          # (n, 3) f32
    diameter: Array          # (n,) f32
    kind: Array              # (n,) i32
    attrs: Dict[str, Array]  # each with n leading rows


@dataclasses.dataclass(frozen=True)
class Observable:
    """A recorded time series: ``fn(state) -> array`` evaluated on the
    post-step state of every iteration whose (pre-increment) step counter is
    ``≡ 0 (mod frequency)`` — ⌈n/k⌉ rows over an n-step run from step 0,
    the same firing rule as :class:`~repro.core.schedule.Operation`.
    ``frequency=0`` disables the observable statically."""

    name: str
    fn: Callable[[Any], Array]
    frequency: int = 1


@dataclasses.dataclass(frozen=True)
class _CustomOp:
    op: Operation
    before: Optional[str] = None
    after: Optional[str] = None
    replaces: Optional[str] = None


def _kind_counts_fn(n_kinds: int) -> Callable[[Any], Array]:
    """The engine's :func:`~repro.core.engine.count_kinds` (which flattens
    any leading device axis, so it serves SimulationState and DistState)
    with a static ``n_kinds`` bound for use under jit/scan."""
    return functools.partial(_engine.count_kinds, n_kinds=int(n_kinds))


class Simulation:
    """Declarative model builder — the single construction path for both
    engines.  Registration methods return ``self`` (chainable or
    imperative); ``build()`` freezes the description into the explicit
    ``(EngineConfig, Scheduler, SimulationState)`` triple.

    Parameters
    ----------
    space:       the cubic simulation space — an extent (``100.0`` means
                 ``[0, 100]``) or explicit ``(min, max)`` bounds.  Stated
                 once: grid spec, boundary clamp, and substance grids all
                 derive from it.
    cell_size:   interaction radius = neighbor-grid box size (≥ the largest
                 interaction distance any behavior queries).  Defaults to
                 the largest registered agent diameter (the Eq-4.1 contact
                 radius).
    boundary:    "open" | "closed" | "toroidal" (§4.4.11).
    dt:          iteration time step.
    capacity:    agent-pool capacity; defaults to the registered population
                 (give headroom for cell division).
    max_per_cell, sort_frequency, diffusion_frequency, use_morton, seed:
                 as in EngineConfig / GridSpec.
    """

    def __init__(
        self,
        space: float | Tuple[float, float],
        cell_size: Optional[float] = None,
        boundary: str = "open",
        dt: float = 1.0,
        capacity: Optional[int] = None,
        max_per_cell: int = 16,
        seed: int = 0,
        sort_frequency: int = 16,
        diffusion_frequency: int = 1,
        use_morton: bool = True,
    ):
        if np.ndim(space) == 0:
            lo, hi = 0.0, float(space)
        else:
            lo, hi = float(space[0]), float(space[1])
        if not hi > lo:
            raise ValueError(f"space must have max > min, got ({lo}, {hi})")
        if boundary not in ("open", "closed", "toroidal"):
            raise ValueError(f"unknown boundary {boundary!r}")
        self.min_bound, self.max_bound = lo, hi
        self.cell_size = None if cell_size is None else float(cell_size)
        self.boundary = boundary
        self.dt = float(dt)
        self.capacity = capacity
        self.max_per_cell = int(max_per_cell)
        self.seed = int(seed)
        self.sort_frequency = int(sort_frequency)
        self.diffusion_frequency = int(diffusion_frequency)
        self.use_morton = bool(use_morton)

        self._groups: List[_AgentGroup] = []
        self._attr_schema: Dict[str, tuple] = {}
        self._grids: Dict[str, dgrid.DiffusionGrid] = {}
        self._behaviors: List[Behavior] = []
        self._force_params: Optional[ForceParams] = None
        self._force_opts: Dict[str, Any] = {}
        self._custom_ops: List[_CustomOp] = []
        self._observables: List[Observable] = []

    # ------------------------------------------------------------ agents

    def add_agents(
        self,
        n: Optional[int] = None,
        *,
        position,
        diameter=10.0,
        kind=0,
        **attrs,
    ) -> "Simulation":
        """Register a group of agents (callable repeatedly; groups share one
        validated SoA attr schema).

        ``position`` is ``(n, 3)`` within the declared space; ``diameter`` /
        ``kind`` and every ``**attrs`` value may be scalar (broadcast) or
        per-agent with ``n`` leading rows.  Attr dtypes/trailing shapes are
        the schema — a later group (or a distributed deployment) declaring
        the same name differently raises at registration time.
        """
        position = jnp.asarray(position, jnp.float32)
        if position.ndim != 2 or position.shape[1] != 3:
            raise ValueError(
                f"position must be (n, 3), got shape {tuple(position.shape)}"
            )
        n_here = int(position.shape[0])
        if n is not None and int(n) != n_here:
            raise ValueError(f"n={n} but position has {n_here} rows")
        pos_np = np.asarray(jax.device_get(position))
        if pos_np.size and (
            pos_np.min() < self.min_bound or pos_np.max() > self.max_bound
        ):
            raise ValueError(
                f"positions outside the declared space "
                f"[{self.min_bound}, {self.max_bound}]: "
                f"range [{pos_np.min():.3g}, {pos_np.max():.3g}]"
            )

        diam = jnp.asarray(
            canonicalize_attr("diameter", diameter, n_here), jnp.float32
        )
        kind_arr = jnp.asarray(canonicalize_attr("kind", kind, n_here))
        if not jnp.issubdtype(kind_arr.dtype, jnp.integer):
            raise TypeError(f"kind must be integer, got dtype {kind_arr.dtype}")
        kind_arr = kind_arr.astype(jnp.int32)

        group_attrs: Dict[str, Array] = {}
        for name, value in attrs.items():
            if name in _RESERVED_ATTRS:
                raise ValueError(
                    f"attr {name!r} is a built-in pool field — pass it via "
                    f"its dedicated argument"
                )
            arr = canonicalize_attr(name, value, n_here)
            if name in self._attr_schema:
                check_attr_schema(name, arr, self._attr_schema)
            group_attrs[name] = arr
        # Strict schema: every group declares every attr (typed SoA — a
        # missing column has no well-defined value for this group's agents).
        missing = set(self._attr_schema) - set(group_attrs)
        extra = set(group_attrs) - set(self._attr_schema) if self._groups else set()
        if missing or extra:
            raise ValueError(
                f"agent groups must share one attr schema: missing "
                f"{sorted(missing)}, new {sorted(extra)} "
                f"(schema so far: {sorted(self._attr_schema)})"
            )
        for name, arr in group_attrs.items():
            self._attr_schema.setdefault(name, attr_signature(arr))

        # A declared capacity is a promise about pool sizing (headroom for
        # division, distributed per-device bounds); blowing through it is a
        # model error best reported at the registration site, naming the
        # offending group — not later as a generic build() failure.
        if self.capacity is not None:
            n_before = sum(g.n for g in self._groups)
            if n_before + n_here > int(self.capacity):
                kinds = np.unique(np.asarray(jax.device_get(kind_arr)))
                raise ValueError(
                    f"add_agents: group of {n_here} agents "
                    f"(kind {kinds.tolist()}) would bring the registered "
                    f"population to {n_before + n_here}, beyond the declared "
                    f"capacity {int(self.capacity)} "
                    f"({n_before} already registered)"
                )

        self._groups.append(
            _AgentGroup(n=n_here, position=position, diameter=diam,
                        kind=kind_arr, attrs=group_attrs)
        )
        return self

    # -------------------------------------------------------- substances

    def add_substance(
        self,
        name: str,
        diffusion: float,
        decay: float = 0.0,
        resolution: int = 32,
        concentration=None,
    ) -> "Simulation":
        """Register an extracellular substance (Eq 4.3) on a
        ``resolution³`` grid over the declared space.  ``concentration``
        optionally sets the initial field (e.g. a static cue)."""
        if name in self._grids:
            raise ValueError(f"substance {name!r} already registered")
        grid = dgrid.make_grid(
            self.min_bound, self.max_bound, int(resolution),
            diffusion_coefficient=float(diffusion),
            decay_constant=float(decay),
        )
        if concentration is not None:
            conc = jnp.asarray(concentration, jnp.float32)
            if conc.shape != grid.concentration.shape:
                raise ValueError(
                    f"substance {name!r}: concentration shape "
                    f"{tuple(conc.shape)} != grid {grid.concentration.shape}"
                )
            grid = dataclasses.replace(grid, concentration=conc)
        self._grids[name] = grid
        return self

    # --------------------------------------------- behaviors / mechanics

    def use(self, *behaviors: Behavior) -> "Simulation":
        """Register agent behaviors (Algorithm 8 L7–11), in execution order."""
        for b in behaviors:
            if not callable(b):
                raise TypeError(f"behavior {b!r} is not callable")
        self._behaviors.extend(behaviors)
        return self

    def mechanics(
        self,
        params: Optional[ForceParams] = ForceParams(),
        impl: str = "reference",
        active_capacity: Optional[int] = None,
        tile: Optional[int] = None,
        overflow_fallback: bool = True,
        diffusion_impl: str = "reference",
        tile_order: str = "linear",
        morton_block: Optional[int] = None,
        morton_window: Optional[int] = None,
        morton_window_fallback: bool = True,
    ) -> "Simulation":
        """Enable Eq-4.1 contact mechanics (+ engine impl knobs).

        ``params=None`` disables the force/static-flag ops (the default when
        this method is never called).  ``impl``/``active_capacity``/``tile``/
        ``overflow_fallback`` map onto the EngineConfig force options;
        ``diffusion_impl`` selects the diffusion kernel.
        ``tile_order="morton"`` (fused impl, single-node) runs the
        Morton-window force kernel over the layout-sorted pool, with the
        ``morton_*`` knobs mapping onto their EngineConfig counterparts.
        """
        self._force_params = params
        self._force_opts = dict(
            force_impl=impl,
            active_capacity=active_capacity,
            force_tile=tile,
            fused_overflow_fallback=overflow_fallback,
            diffusion_impl=diffusion_impl,
            tile_order=tile_order,
            morton_block=morton_block,
            morton_window=morton_window,
            morton_window_fallback=morton_window_fallback,
        )
        return self

    # -------------------------------------------------------- operations

    def op(
        self,
        fn,
        *,
        name: Optional[str] = None,
        phase: str = "post",
        frequency: int = 1,
        gate: str = "cond",
        before: Optional[str] = None,
        after: Optional[str] = None,
        replaces: Optional[str] = None,
    ) -> "Simulation":
        """Register a custom scheduler operation (DESIGN.md §5).

        ``fn`` is a pure ``(OpContext, state) -> state`` transform (or a
        ready-made :class:`~repro.core.schedule.Operation`, in which case
        the wrapping arguments must be left at their defaults).  At most one
        of ``before=``/``after=``/``replaces=`` anchors it by op name;
        default is appending.  Applied identically to the single-node and
        distributed schedules — the distributed pipeline shares the anchor
        names (DESIGN.md §5).
        """
        if sum(x is not None for x in (before, after, replaces)) > 1:
            raise ValueError("pass at most one of before=/after=/replaces=")
        if isinstance(fn, Operation):
            if name is not None or (phase, frequency, gate) != ("post", 1, "cond"):
                raise ValueError(
                    "pass scheduling fields on the Operation itself when "
                    "registering a ready-made Operation"
                )
            operation = fn
        else:
            if name is None:
                name = getattr(fn, "__name__", None)
                if not name or name == "<lambda>":
                    raise ValueError("op(fn) needs name= for anonymous functions")
            operation = Operation(
                name=name, fn=fn, phase=phase, frequency=frequency, gate=gate
            )
        self._custom_ops.append(
            _CustomOp(op=operation, before=before, after=after, replaces=replaces)
        )
        return self

    # ------------------------------------------------------- observables

    def observe(self, name: str, fn: Callable, frequency: int = 1) -> "Simulation":
        """Record ``fn(state)`` as a named time series carried through the
        ``lax.scan`` ys: ⌈n/k⌉ rows over an n-step run (see
        :class:`Observable`).  Returned by ``run``/``run_jit`` as
        ``obs[name]`` with the recorded rows stacked on axis 0."""
        if any(o.name == name for o in self._observables):
            raise ValueError(f"observable {name!r} already registered")
        if not isinstance(frequency, (int, np.integer)) or frequency < 0:
            raise ValueError(
                f"frequency must be a non-negative int, got {frequency!r}"
            )
        self._observables.append(
            Observable(name=name, fn=fn, frequency=int(frequency))
        )
        return self

    def observe_kinds(
        self, name: str = "kind_counts", frequency: int = 1,
        n_kinds: Optional[int] = None,
    ) -> "Simulation":
        """Built-in observable: per-kind alive counts (the Fig-4.17 SIR
        curves).  ``n_kinds`` defaults to ``max(registered kinds) + 1`` —
        pass it explicitly when dynamics can reach kinds not initially
        present (e.g. RECOVERED)."""
        if n_kinds is None:
            if not self._groups:
                raise ValueError(
                    "observe_kinds before add_agents needs explicit n_kinds="
                )
            n_kinds = 1 + max(
                int(jax.device_get(g.kind).max()) if g.n else 0
                for g in self._groups
            )
        return self.observe(name, _kind_counts_fn(int(n_kinds)), frequency)

    # ------------------------------------------------------------- build

    def interaction_radius(self) -> float:
        """The derived neighbor-grid box size: explicit ``cell_size``, else
        the largest registered diameter (the Eq-4.1 contact reach)."""
        if self.cell_size is not None:
            return self.cell_size
        if not self._groups:
            raise ValueError("no agents registered — call add_agents first")
        d = max(float(jax.device_get(g.diameter).max()) for g in self._groups)
        if d <= 0.0:
            raise ValueError(
                "cannot derive cell_size from zero diameters — pass "
                "cell_size= explicitly"
            )
        return d

    def _capacity(self) -> int:
        n_total = sum(g.n for g in self._groups)
        return n_total if self.capacity is None else int(self.capacity)

    def _pool(self):
        if not self._groups:
            raise ValueError("no agents registered — call add_agents first")
        n_total = sum(g.n for g in self._groups)
        capacity = self._capacity()
        if n_total > capacity:
            raise ValueError(
                f"{n_total} registered agents exceed capacity {capacity}"
            )
        cat = lambda xs: jnp.concatenate(xs, axis=0)
        return make_pool(
            capacity,
            cat([g.position for g in self._groups]),
            diameter=cat([g.diameter for g in self._groups]),
            kind=cat([g.kind for g in self._groups]),
            attrs={
                name: cat([g.attrs[name] for g in self._groups])
                for name in self._attr_schema
            },
        )

    def _engine_config(self) -> EngineConfig:
        spec = spec_for_space(
            self.min_bound,
            self.max_bound,
            self.interaction_radius(),
            max_per_cell=self.max_per_cell,
            use_morton=self.use_morton,
        )
        return EngineConfig(
            spec=spec,
            behaviors=tuple(self._behaviors),
            force_params=self._force_params,
            dt=self.dt,
            min_bound=self.min_bound,
            max_bound=self.max_bound,
            boundary=self.boundary,
            sort_frequency=self.sort_frequency,
            diffusion_frequency=self.diffusion_frequency,
            **self._force_opts,
        )

    def _apply_custom_ops(self, sched: Scheduler) -> Scheduler:
        for c in self._custom_ops:
            if c.replaces is not None:
                sched = sched.replace_op(c.replaces, c.op)
            elif c.before is not None:
                sched = sched.insert_before(c.before, c.op)
            elif c.after is not None:
                sched = sched.insert_after(c.after, c.op)
            else:
                sched = sched.append(c.op)
        return sched

    def build(self, seed: Optional[int] = None) -> "BuiltSimulation":
        """Compile the description into the explicit engine triple.

        Returns a :class:`BuiltSimulation` wrapping ``(EngineConfig,
        Scheduler, SimulationState)`` — exactly what the hand-wired pipeline
        constructs, via the same primitives, so the two are bit-exact.
        """
        config = self._engine_config()
        scheduler = self._apply_custom_ops(Scheduler.default(config))
        state = init_state(
            self._pool(), dict(self._grids),
            seed=self.seed if seed is None else seed,
        )
        return BuiltSimulation(
            config=config,
            scheduler=scheduler,
            state=state,
            observables=tuple(self._observables),
        )

    # -------------------------------------------------------- execution

    def run(self, n_steps: int, seed: Optional[int] = None, **run_kwargs):
        """Build + run un-jitted (tracing/debugging); fresh initial state.
        ``checkpoint_dir=`` / ``checkpoint_every=`` pass through to
        :meth:`BuiltSimulation.run` for fault-tolerant runs."""
        return self.build(seed=seed).run(n_steps, **run_kwargs)

    def run_jit(self, n_steps: int, seed: Optional[int] = None, **run_kwargs):
        """Build + run under jit; fresh initial state.  For chunked runs
        (evolving state across calls) use ``build()`` and the
        :class:`BuiltSimulation` methods.  ``checkpoint_dir=`` /
        ``checkpoint_every=`` pass through for fault-tolerant runs."""
        return self.build(seed=seed).run_jit(n_steps, **run_kwargs)

    def run_batch(self, n_steps: int,
                  params: Optional[Dict[str, Any]] = None, *,
                  seeds: Optional[Sequence[int]] = None,
                  batch: Optional[int] = None, seed: Optional[int] = None):
        """Build + sweep B independent variants of this model through one
        compiled batched scan → ``(stacked finals, {name: (B, rows, ...)})``.
        See :meth:`BuiltSimulation.run_batch` for the override namespace;
        slot b is bit-exactly the solo ``run_jit`` of that variant."""
        return self.build(seed=seed).run_batch(
            n_steps, params, seeds=seeds, batch=batch
        )

    def resume(self, checkpoint_dir: str, seed: Optional[int] = None,
               **resume_kwargs):
        """Rebuild this model and finish an interrupted checkpointed run —
        ``Simulation.resume(dir)`` alone recovers a killed
        ``run(..., checkpoint_dir=dir)`` bit-exactly (the checkpoint's
        manifest records the target step and interval).  The description
        must match the one that wrote the checkpoint; shape/dtype
        validation at restore enforces that."""
        return self.build(seed=seed).resume(checkpoint_dir, **resume_kwargs)

    def distribute(self, mesh, dcfg, capacity: Optional[int] = None,
                   seed: Optional[int] = None) -> "DistributedSimulation":
        """Deploy the same model description onto a device mesh (Ch. 6).

        ``dcfg`` (a :class:`~repro.core.distributed.DomainConfig`) chooses
        the decomposition; it must tile the declared space (``extent ×
        axis_size`` per decomposed dim, ``depth`` = full extent on the
        rest).  Agents are binned to devices, substances domain-split, and
        the same behaviors / mechanics / custom ops / observables run
        through the distributed schedule — distribution is a deployment
        choice, not a model change.  ``capacity`` is per device (default:
        the single-node capacity, a safe bound).
        """
        from . import distributed as dist

        extent_total = self.max_bound - self.min_bound
        for d in range(dcfg.n_decomposed):
            want = extent_total / dcfg.axis_sizes[d]
            if abs(dcfg.extent - want) > 1e-6 * max(extent_total, 1.0):
                raise ValueError(
                    f"DomainConfig.extent {dcfg.extent} × axis_sizes[{d}]="
                    f"{dcfg.axis_sizes[d]} does not tile the declared space "
                    f"extent {extent_total} (want extent {want})"
                )
        if dcfg.n_decomposed < 3 and abs(dcfg.depth - extent_total) > 1e-6 * max(
            extent_total, 1.0
        ):
            raise ValueError(
                f"DomainConfig.depth {dcfg.depth} must equal the space extent "
                f"{extent_total} on non-decomposed dims"
            )
        radius = self.interaction_radius()
        if dcfg.halo_width < radius - 1e-9:
            raise ValueError(
                f"DomainConfig.halo_width {dcfg.halo_width} < interaction "
                f"radius {radius}: remote neighbors would be missed"
            )

        # The single-node config with only the deployment-specific fields
        # swapped: the halo-extended grid and the local coordinate frame.
        # One field list (in _engine_config) — a new engine knob surfaced on
        # the facade reaches both deployments by construction.
        ecfg = dataclasses.replace(
            self._engine_config(),
            spec=dcfg.grid_spec(box_size=radius,
                                max_per_cell=self.max_per_cell,
                                use_morton=self.use_morton),
            min_bound=0.0,
            max_bound=extent_total,
        )
        scheduler = self._apply_custom_ops(dist.distributed_scheduler(dcfg, ecfg))

        # Global description → per-device state: positions shifted to the
        # origin (local frames), substances split along the decomposed dims.
        if not self._groups:
            raise ValueError("no agents registered — call add_agents first")
        g = lambda arrs: np.concatenate([np.asarray(jax.device_get(a)) for a in arrs])
        positions = g([grp.position for grp in self._groups]) - self.min_bound
        diameter = g([grp.diameter for grp in self._groups])
        kind = g([grp.kind for grp in self._groups])
        attrs = {
            name: g([grp.attrs[name] for grp in self._groups])
            for name in self._attr_schema
        }
        state = dist.init_dist_state(
            dcfg,
            capacity=self._capacity() if capacity is None else int(capacity),
            positions=positions.astype(np.float32),
            diameter=diameter,
            kind=kind,
            seed=self.seed if seed is None else seed,
            attrs=attrs,
            stacked_grids=self._split_grids(dcfg),
        )
        step = dist.make_distributed_step(mesh, dcfg, ecfg, scheduler=scheduler)
        return DistributedSimulation(
            mesh=mesh,
            dcfg=dcfg,
            config=ecfg,
            scheduler=scheduler,
            state=state,
            step=step,
            observables=tuple(self._observables),
        )

    def _split_grids(self, dcfg) -> Dict[str, dgrid.DiffusionGrid]:
        """Split each global substance grid into per-device local grids
        (stacked on a leading device axis), in the device-local frame
        (origin 0) matching the rebased agent coordinates.

        Uneven splits use *ghost-voxel padding*: every device carries a
        uniform ``ceil(R/S)``-voxel frame (static SPMD shapes); devices
        past the end of the global lattice pad with zeros, and the grid's
        ``n_valid`` / ``frame_shift`` metadata masks the padding out of
        diffusion and sampling (see :class:`~repro.core.diffusion
        .DiffusionGrid`).  A resolution smaller than the mesh still raises
        (some device would own no voxels at all along the short dim),
        as does an uneven split under a toroidal boundary (the padded face
        would break the periodic wrap alignment)."""
        out: Dict[str, dgrid.DiffusionGrid] = {}
        nd = dcfg.n_decomposed
        for name, grid in self._grids.items():
            res = grid.concentration.shape
            small = [d for d in range(nd) if res[d] < dcfg.axis_sizes[d]]
            if small:
                detail = ", ".join(
                    f"dim {d}: {res[d]} < {dcfg.axis_sizes[d]}" for d in small
                )
                raise ValueError(
                    f"substance {name!r}: resolution smaller than the mesh "
                    f"on dims {small} ({detail}); every decomposed dim needs "
                    f"at least one voxel per device"
                )
            uneven = [d for d in range(nd) if res[d] % dcfg.axis_sizes[d] != 0]
            if uneven and self.boundary == "toroidal":
                raise ValueError(
                    f"substance {name!r}: uneven split on dims {uneven} with "
                    f"a toroidal boundary — ghost-voxel padding would break "
                    f"the periodic wrap alignment; pick a resolution "
                    f"divisible by the device counts on every decomposed dim"
                )
            per = [
                -(-res[d] // dcfg.axis_sizes[d]) if d < nd else res[d]
                for d in range(3)
            ]
            conc = np.asarray(jax.device_get(grid.concentration))
            locals_ = []
            for dev in range(dcfg.n_devices):
                coords = list(dcfg.device_coords(dev)) + [0] * (3 - nd)
                lo = [coords[d] * per[d] if d < nd else 0 for d in range(3)]
                block = conc[tuple(
                    slice(lo[d], min(lo[d] + per[d], res[d])) for d in range(3)
                )]
                block = np.pad(
                    block, [(0, per[d] - block.shape[d]) for d in range(3)]
                )
                extra = {}
                if uneven:
                    extra = dict(
                        n_valid=jnp.asarray(
                            [
                                min(per[d], max(res[d] - lo[d], 0))
                                if d < nd else res[d]
                                for d in range(3)
                            ],
                            jnp.int32,
                        ),
                        frame_shift=jnp.asarray(
                            [
                                lo[d] * grid.spacing - coords[d] * dcfg.extent
                                if d < nd else 0.0
                                for d in range(3)
                            ],
                            jnp.float32,
                        ),
                    )
                locals_.append(
                    dataclasses.replace(
                        grid,
                        concentration=jnp.asarray(block),
                        origin=(0.0, 0.0, 0.0),
                        **extra,
                    )
                )
            out[name] = jax.tree.map(lambda *xs: jnp.stack(xs), *locals_)
        return out


# ---------------------------------------------------------------------------
# Built artifacts
# ---------------------------------------------------------------------------


def _slice_observed(
    observables, ys: Dict[str, Array], start: int, n_steps: int
) -> Dict[str, Array]:
    """Trim each observable's rows to the firings actually in the window.

    Iteration i (counter ``start + i``) records when the counter is
    ``≡ 0 (mod k)`` — from a step-0 start that is ⌈n/k⌉ rows, mirroring
    Operation frequency semantics.  Frequency-1 series come back exact from
    the scan ys; frequency-k ones come back in a ⌈n/k⌉-row device buffer
    whose tail is unwritten when the start step is misaligned — the firing
    count is computable here (the start step is concrete), so slice it."""
    out: Dict[str, Array] = {}
    for o in observables:
        k = o.frequency
        if k == 0:
            continue
        if k == 1:
            out[o.name] = ys[o.name]
            continue
        first = (-start) % k                      # first firing offset
        fired = 0 if first >= n_steps else -(-(n_steps - first) // k)
        out[o.name] = ys[o.name][:fired]
    return out


# --------------------------------------------------------------- checkpoints

#: Manifest meta format tag — bumped when the persisted payload layout
#: changes, so ``resume`` rejects checkpoints from an incompatible writer
#: instead of mis-restoring them.
CKPT_FORMAT = "abm-run/1"


def _step_of(state) -> int:
    """The concrete absolute step counter (first device's on DistState —
    all devices advance in lockstep)."""
    return int(np.asarray(jax.device_get(state.step)).ravel()[0])


def _concat_obs(acc: Dict[str, np.ndarray], new) -> Dict[str, np.ndarray]:
    out = dict(acc)
    for name, rows in new.items():
        rows = np.asarray(jax.device_get(rows))
        prev = out.get(name)
        out[name] = rows if prev is None else np.concatenate([prev, rows], 0)
    return out


def _checkpointed_loop(
    run_chunk: Callable[[int, Any], Tuple[Any, Dict[str, Array]]],
    state,
    n_steps: int,
    *,
    engine: str,
    checkpoint_dir: str,
    checkpoint_every: Optional[int],
    keep: int,
    on_chunk: Optional[Callable[[Any], None]],
    obs_acc: Optional[Dict[str, np.ndarray]] = None,
    target_step: Optional[int] = None,
):
    """Drive ``run_chunk`` in checkpoint-interval chunks up to the target.

    The persisted tree is the *full run pytree* — simulation state (pool,
    grids, RNG key data, step counter, health) plus every observable row
    recorded so far — so a resume returns the identical final state AND the
    identical complete series an uninterrupted run would have.  Chunking is
    invisible to the dynamics: the per-step RNG folds the absolute step
    counter, so k-step chunks compose bit-exactly into one long scan
    (tests/test_checkpoint.py proves 2k straight == k + kill + resume + k).

    An anchor checkpoint is written *before* the first chunk so a crash
    inside it resumes from the true beginning; ``on_chunk(state)`` fires
    after each save — the fault-injection tier kills the process there.
    """
    every = int(checkpoint_every) if checkpoint_every else int(n_steps)
    if every <= 0:
        raise ValueError(f"checkpoint_every must be positive, got {every}")
    start = _step_of(state)
    target = start + int(n_steps) if target_step is None else int(target_step)
    acc = {k: np.asarray(v) for k, v in (obs_acc or {}).items()}

    def save(st):
        _ckpt.save(
            checkpoint_dir,
            _step_of(st),
            {"state": st, "obs": acc},
            keep=keep,
            meta={
                "format": CKPT_FORMAT,
                "engine": engine,
                "target_step": target,
                "checkpoint_every": every,
                "obs_rows": {k: int(v.shape[0]) for k, v in acc.items()},
            },
        )

    save(state)
    while _step_of(state) < target:
        chunk = min(every, target - _step_of(state))
        state, obs = run_chunk(chunk, state)
        acc = _concat_obs(acc, obs)
        save(state)
        if on_chunk is not None:
            on_chunk(state)
    return state, {k: jnp.asarray(v) for k, v in acc.items()}


def _resume_payload(checkpoint_dir: str, engine: str, proto_state, observables):
    """Validate + restore the latest run checkpoint against this model.

    Strict by construction: the ``like`` tree is the *built* initial state
    (so every pool/grid/rng/health leaf is shape- and dtype-checked by
    ``checkpoint.restore``) plus per-observable row buffers sized from the
    manifest's ``obs_rows`` and typed from ``jax.eval_shape`` protos.  A
    checkpoint from a different model, capacity, engine, or writer fails
    loudly here instead of corrupting the resumed run.
    """
    step, manifest = _ckpt.read_manifest(checkpoint_dir)
    meta = manifest.get("meta") or {}
    if meta.get("format") != CKPT_FORMAT:
        raise ValueError(
            f"{checkpoint_dir} step {step} is not an ABM run checkpoint "
            f"(manifest meta format {meta.get('format')!r}, want "
            f"{CKPT_FORMAT!r}) — was it written by checkpoint.save directly?"
        )
    if meta.get("engine") != engine:
        raise ValueError(
            f"checkpoint at {checkpoint_dir} was written by the "
            f"{meta.get('engine')!r} engine and cannot resume on {engine!r}"
        )
    live = [o for o in observables if o.frequency > 0]
    protos = jax.eval_shape(
        lambda s: {o.name: o.fn(s) for o in live}, proto_state
    )
    rows = meta.get("obs_rows") or {}
    like_obs = {
        name: jax.ShapeDtypeStruct(
            (int(rows.get(name, 0)),) + tuple(p.shape), p.dtype
        )
        for name, p in protos.items()
    }
    # checkpoint.restore tolerates extra arrays (``like`` may be a
    # sub-structure); a *resume* is stricter — the model must account for
    # every persisted array, or it is not the model that wrote the run.
    n_like = len(jax.tree_util.tree_leaves({"state": proto_state,
                                            "obs": like_obs}))
    n_saved = manifest.get("n_arrays")
    if n_saved is not None and n_saved != n_like:
        raise ValueError(
            f"checkpoint at {checkpoint_dir} holds {n_saved} arrays but "
            f"this model expects {n_like} — stale or foreign checkpoint"
        )
    _, payload = _ckpt.restore(
        checkpoint_dir, {"state": proto_state, "obs": like_obs}, step=step
    )
    state = jax.tree.map(jnp.asarray, payload["state"])
    acc = {k: np.asarray(v) for k, v in payload["obs"].items()}
    return step, state, acc, int(meta["target_step"]), int(
        meta.get("checkpoint_every") or 1
    )


@dataclasses.dataclass(frozen=True)
class BuiltSimulation:
    """The compiled model: the explicit engine triple + observables.

    ``config``/``scheduler``/``state`` are exactly the objects the
    hand-wired pipeline constructs — the facade is a construction shorthand,
    not a second engine.  ``run``/``run_jit`` default to the built initial
    state; pass ``state=`` to continue an evolved one (chunked runs).
    """

    config: EngineConfig
    scheduler: Scheduler
    state: SimulationState
    observables: Tuple[Observable, ...] = ()

    def _obs_triples(self):
        return tuple(
            (o.name, o.fn, o.frequency)
            for o in self.observables if o.frequency > 0
        )

    @functools.cached_property
    def _runner_cache(self):
        # One runner per execution signature, for the BuiltSimulation's
        # lifetime — nothing global.  Keyed so the solo jit wrapper and the
        # batched (vmapped) engine coexist: ``("solo",)`` holds the scalar
        # jit wrapper (chunked runs reuse its compiled scan), ``("batch",)``
        # holds the BatchedSimulation whose own wrapper keys on the slot
        # width — mixing run_jit and run_batch never evicts or re-traces
        # the other's program (regression: tests/test_batch.py).
        return {}

    @property
    def _jitted(self):
        cache = self._runner_cache
        if ("solo",) not in cache:
            cache[("solo",)] = _engine.jitted_runner(
                self.config, self.scheduler
            )
        return cache[("solo",)]

    def _execute(self, n_steps: int, state, jit: bool):
        state = self.state if state is None else state
        with jax.profiler.TraceAnnotation(spans.READ_STEP):
            start = int(jax.device_get(state.step))
        triples = self._obs_triples()
        if jit:
            with jax.profiler.TraceAnnotation(spans.LAUNCH):
                final, ys = self._jitted(
                    state, n_steps=n_steps, observables=triples or None
                )
        else:
            final, ys = _engine.run(
                self.config, state, n_steps,
                scheduler=self.scheduler, observables=triples or None,
            )
        obs = (
            _slice_observed(self.observables, ys, start, n_steps)
            if triples else {}
        )
        return final, obs

    def run(self, n_steps: int, state: Optional[SimulationState] = None,
            *, checkpoint_dir: Optional[str] = None,
            checkpoint_every: Optional[int] = None, keep: int = 3,
            on_chunk: Optional[Callable[[Any], None]] = None):
        """Un-jitted ``lax.scan`` run → ``(final_state, {name: rows})``.

        With ``checkpoint_dir=`` the run is chunked into
        ``checkpoint_every``-step scans, persisting the full run pytree
        (state + observable rows so far) after each — kill the process at
        any point and :meth:`resume` finishes the run bit-exactly.
        """
        if checkpoint_dir is None:
            return self._execute(n_steps, state, jit=False)
        return self._run_checkpointed(
            n_steps, state, False, checkpoint_dir, checkpoint_every, keep,
            on_chunk,
        )

    def run_jit(self, n_steps: int, state: Optional[SimulationState] = None,
                *, checkpoint_dir: Optional[str] = None,
                checkpoint_every: Optional[int] = None, keep: int = 3,
                on_chunk: Optional[Callable[[Any], None]] = None):
        """Jitted run → ``(final_state, {name: rows})``.  Checkpointing as
        in :meth:`run`; the chunks reuse one compiled scan per chunk size."""
        if checkpoint_dir is None:
            return self._execute(n_steps, state, jit=True)
        return self._run_checkpointed(
            n_steps, state, True, checkpoint_dir, checkpoint_every, keep,
            on_chunk,
        )

    def _run_checkpointed(self, n_steps, state, jit, checkpoint_dir,
                          checkpoint_every, keep, on_chunk,
                          obs_acc=None, target_step=None):
        state = self.state if state is None else state
        return _checkpointed_loop(
            lambda k, st: self._execute(k, st, jit=jit),
            state, n_steps, engine="single",
            checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
            keep=keep, on_chunk=on_chunk, obs_acc=obs_acc,
            target_step=target_step,
        )

    def resume(self, checkpoint_dir: str, *, jit: bool = True, keep: int = 3,
               on_chunk: Optional[Callable[[Any], None]] = None):
        """Finish an interrupted checkpointed run → the same
        ``(final_state, {name: rows})`` the uninterrupted run returns.

        Restores the latest valid checkpoint (strictly validated against
        this model's built state — see :func:`_resume_payload`), then runs
        the remaining ``target_step − restored_step`` iterations under the
        recorded checkpoint interval.  Bit-exact: per-step RNG folds the
        absolute step counter, so resumed dynamics are the straight-through
        run's; the returned series is restored rows + new rows.
        """
        step, state, acc, target, every = _resume_payload(
            checkpoint_dir, "single", self.state, self.observables
        )
        if target - step <= 0:
            return state, {k: jnp.asarray(v) for k, v in acc.items()}
        return self._run_checkpointed(
            target - step, state, jit, checkpoint_dir, every, keep, on_chunk,
            obs_acc=acc, target_step=target,
        )

    # ---------------------------------------------------- batched serving

    def batched(self):
        """The many-simulation engine for this model (DESIGN.md §8): a
        :class:`~repro.core.batch.BatchedSimulation` vmapping the same
        scheduler step over a leading slot axis of independent session
        states, with the built state as the validation template.  Cached in
        the runner cache alongside the solo jit wrapper, so batched and
        solo compiles coexist for the model's lifetime."""
        from . import batch as _batch

        cache = self._runner_cache
        if ("batch",) not in cache:
            cache[("batch",)] = _batch.BatchedSimulation(
                self.config, self.scheduler, self.state, self.observables
            )
        return cache[("batch",)]

    def run_batch(self, n_steps: int, params: Optional[Dict[str, Any]] = None,
                  *, seeds: Optional[Sequence[int]] = None,
                  batch: Optional[int] = None):
        """Sweep B parameter variants through ONE compiled scan.

        ``params`` maps override keys to per-slot values with a leading
        slot axis: ``"attr:NAME"`` sets initial agent-attr values (scalar
        per slot, or per-agent over the registered agents), and
        ``"substance:NAME"`` sets initial concentrations (uniform scalar
        per slot, or a full field) — per-slot *op constants* ride as attrs
        the op reads.  ``seeds`` gives slot ``b`` its own
        ``PRNGKey(seeds[b])`` stream (default: ``fold_in(built_rng, b)``);
        ``batch`` forces the width when neither implies it.

        Returns ``(finals, obs)``: the stacked final states (every leaf
        with a leading B axis — ``jax.tree.map(lambda l: l[b], finals)``
        is slot b's final state) and ``obs[name]`` of shape
        ``(B, rows, ...)``.  Bit-exact per slot: slot b equals a solo
        ``run_jit`` of that variant (asserted in tests/test_batch.py and
        in-bench by benchmarks/bench_many_sim.py).
        """
        eng = self.batched()
        bstate = eng.sweep_state(batch=batch, seeds=seeds, params=params)
        bstate, obs, counts = eng.run_jit(bstate, n_steps)
        # Sweep slots share the built start step, so every slot fired the
        # same rows — trim the ⌈n/k⌉-row buffers once, host-side.
        if obs:
            fired = {
                k: int(np.asarray(jax.device_get(v))[0])
                for k, v in counts.items()
            }
            obs = {k: v[:, : fired[k]] for k, v in obs.items()}
        return bstate.states, obs


@dataclasses.dataclass(frozen=True)
class DistributedSimulation:
    """The same model deployed on a mesh: per-device state + jitted step.

    ``run`` drives the shard_mapped step from the host; observables are
    evaluated on the *stacked* state (the built-in kind-counts observable is
    stack-agnostic; custom observables that index pool arrays should reshape
    over the leading device axis).
    """

    mesh: Any
    dcfg: Any
    config: EngineConfig
    scheduler: Scheduler
    state: Any                       # DistState
    step: Callable[[Any], Any]
    observables: Tuple[Observable, ...] = ()

    def run(self, n_steps: int, state=None,
            *, checkpoint_dir: Optional[str] = None,
            checkpoint_every: Optional[int] = None, keep: int = 3,
            on_chunk: Optional[Callable[[Any], None]] = None):
        """Step ``n_steps`` iterations → ``(final_state, {name: rows})``.

        ``checkpoint_dir=`` persists the full distributed run pytree (the
        stacked ``DistState`` + observable rows) every ``checkpoint_every``
        steps, exactly like ``BuiltSimulation.run`` — :meth:`resume`
        finishes a killed run bit-exactly on the same mesh shape.
        """
        state = self.state if state is None else state
        if checkpoint_dir is None:
            return self._run_chunk(n_steps, state)
        return _checkpointed_loop(
            self._run_chunk, state, n_steps, engine="dist",
            checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
            keep=keep, on_chunk=on_chunk,
        )

    def _run_chunk(self, n_steps: int, state):
        live = [o for o in self.observables if o.frequency > 0]
        rows: Dict[str, List[Array]] = {o.name: [] for o in live}
        # One host sync for the counter; it advances by exactly 1 per step,
        # so the loop stays asynchronous (no per-step device_get).
        with jax.profiler.TraceAnnotation(spans.READ_STEP):
            start = int(np.asarray(jax.device_get(state.step)).ravel()[0])
        for i in range(n_steps):
            with jax.profiler.TraceAnnotation(spans.LAUNCH):
                state = self.step(state)
            for o in live:
                if (start + i) % o.frequency == 0:
                    rows[o.name].append(o.fn(state))
        obs = {}
        for o in live:
            r = rows[o.name]
            if r:
                obs[o.name] = jnp.stack(r)
            else:
                # Zero firings: keep the observable's real row shape/dtype
                # (mirrors the single-node path's empty slice).
                proto = o.fn(state)
                obs[o.name] = jnp.zeros((0,) + proto.shape, proto.dtype)
        return state, obs

    def resume(self, checkpoint_dir: str, *, keep: int = 3,
               on_chunk: Optional[Callable[[Any], None]] = None):
        """Finish an interrupted distributed checkpointed run (see
        ``BuiltSimulation.resume``).  The checkpoint's per-device shapes are
        validated against this deployment's built state, so resuming on a
        different mesh shape or capacity fails loudly."""
        step, state, acc, target, every = _resume_payload(
            checkpoint_dir, "dist", self.state, self.observables
        )
        if target - step <= 0:
            return state, {k: jnp.asarray(v) for k, v in acc.items()}
        return _checkpointed_loop(
            self._run_chunk, state, target - step, engine="dist",
            checkpoint_dir=checkpoint_dir, checkpoint_every=every, keep=keep,
            on_chunk=on_chunk, obs_acc=acc, target_step=target,
        )
