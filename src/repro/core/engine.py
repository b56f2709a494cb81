"""The simulation engine: Algorithm 8 as a pure JAX step function.

BioDynaMo's scheduler executes, per iteration: pre-standalone operations
(environment build), the agent-op loop (behaviors + mechanical forces), and
post-standalone operations (diffusion, visualization export).  Operations
carry *execution frequencies* (§4.4.4 multi-scale support).

The schedule itself lives in `core/schedule.py` (DESIGN.md §5): a
:class:`~repro.core.schedule.Scheduler` composes named, phase-tagged,
frequency-gated :class:`~repro.core.schedule.Operation` values, and
:func:`simulation_step` is nothing but ``Scheduler.default(config).step`` —
the same scheduler the distributed engine (`core/distributed.py`) runs with
distribution expressed as ops.  Insert / replace / remove ops on a schedule
to add functionality without touching this module.

The entire iteration is a pure function ``state' = step(config, state)`` so
the loop is a ``lax.scan`` (checkpointable, differentiable-if-wanted, and
the distributed engine wraps the same pipeline in ``shard_map``).
Frequencies lower per-op as ``lax.cond`` (skip expensive work: sorting,
diffusion) or as predicated mod-mask selects (cheap ops on TPU), chosen by
each op's ``gate``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import spans
from . import diffusion as dgrid
from .agents import AgentPool
from .behaviors import Behavior
from .diffusion import DIFFUSION_IMPLS
from .forces import FORCE_IMPLS, TILE_ORDERS, ForceParams
from .grid import GridSpec
from .schedule import HealthReport, Scheduler, empty_health

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static engine configuration (not a pytree — baked into the jit)."""

    spec: GridSpec
    behaviors: Tuple[Behavior, ...] = ()
    force_params: Optional[ForceParams] = None       # None → no mechanics op
    dt: float = 1.0
    min_bound: float = 0.0
    max_bound: float = 100.0
    boundary: str = "open"                           # open | closed | toroidal
    sort_frequency: int = 16                         # §5.4.2 / Fig 5.14
    diffusion_frequency: int = 1                     # §4.4.4 multi-scale
    active_capacity: Optional[int] = None            # §5.5 work compaction
    force_tile: Optional[int] = None                 # tile-wise force eval
    force_impl: str = "reference"                    # FORCE_IMPLS
    diffusion_impl: str = "reference"                # DIFFUSION_IMPLS
    # "fused" only: lax.cond back to the dense candidate path when a cell
    # overflows max_per_cell (cell-list truncation would drop pair forces).
    # Disable only when max_per_cell is a guaranteed bound; that keeps the
    # dense path out of the compiled step entirely.  (Combining "fused" with
    # active_capacity composes: the compacted branch builds an (A, 27M)
    # subset via NeighborContext.candidates_for, never the dense (C, 27M)
    # tensor — see mechanical_forces.)
    fused_overflow_fallback: bool = True
    # "fused" only: force-tile iteration order.  "morton" runs the Morton-
    # window kernel over the layout-sorted pool (storage-order tiles, ± a
    # window of contiguous blocks — §5.4.2's locality payoff), guarded per
    # step by a coverage check with lax.cond fallback to the linear path
    # (morton_window_fallback; disable only for compile-cost benchmarks on
    # known-sorted layouts).  block/window default per pool size — see
    # repro.kernels.cell_force.ops.window_defaults.
    tile_order: str = "linear"                       # TILE_ORDERS
    morton_block: Optional[int] = None
    morton_window: Optional[int] = None
    morton_window_fallback: bool = True
    # Health-telemetry op frequency (DESIGN.md §7): fold saturation /
    # non-finite detection into state.health every k steps (0 disables).
    health_frequency: int = 1

    def __post_init__(self):
        for name, choices in (
            ("force_impl", FORCE_IMPLS),
            ("diffusion_impl", DIFFUSION_IMPLS),
            ("tile_order", TILE_ORDERS),
        ):
            value = getattr(self, name)
            if value not in choices:
                raise ValueError(
                    f"unknown {name} {value!r}; expected one of {choices}"
                )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SimulationState:
    pool: AgentPool
    grids: Dict[str, dgrid.DiffusionGrid]
    rng: Array
    step: Array  # i32 iteration counter
    health: HealthReport  # saturation / corruption telemetry (DESIGN.md §7)


def init_state(
    pool: AgentPool,
    grids: Optional[Dict[str, dgrid.DiffusionGrid]] = None,
    seed: int = 0,
) -> SimulationState:
    return SimulationState(
        pool=pool,
        grids=dict(grids or {}),
        rng=jax.random.PRNGKey(seed),
        step=jnp.zeros((), jnp.int32),
        health=empty_health(),
    )


def simulation_step(config: EngineConfig, state: SimulationState) -> SimulationState:
    """One iteration of Algorithm 8 (the default schedule)."""
    return Scheduler.default(config).step(state)


@functools.partial(jax.profiler.annotate_function, name=spans.TRACE_SCHEDULE)
def run(
    config: EngineConfig,
    state: SimulationState,
    n_steps: int,
    collect: Optional[Callable[[SimulationState], jax.Array | dict]] = None,
    scheduler: Optional[Scheduler] = None,
    observables: Optional[Tuple[Tuple[str, Callable, int], ...]] = None,
):
    """Run ``n_steps`` iterations under ``lax.scan``.

    ``collect`` optionally extracts per-step observables (e.g. SIR counts);
    ``observables`` is the model-API form of the same thing — a static tuple
    of ``(name, fn, frequency)`` triples, each ``fn(state) -> array``
    evaluated on the post-step state of iterations whose (pre-increment)
    step counter is ``≡ 0 (mod frequency)``.  Frequency-1 observables ride
    the scan ys (one row per step); frequency-k ones record *in-scan* into a
    ``⌈n/k⌉``-row carry buffer via a counter-gated ``lax.cond`` — the fn is
    only evaluated on firing iterations and non-firing rows never
    materialize (an every-100-steps field snapshot costs 1/100th, not 100×).
    Returned as ``{name: rows}``; buffer rows beyond the window's actual
    firing count (possible when the start step is not ≡ 0 mod k) stay zero —
    the :class:`~repro.core.api.Simulation` facade, which knows the concrete
    start step, slices them off.  ``collect`` and ``observables`` are
    mutually exclusive.  ``scheduler`` overrides the default operation
    schedule (custom ops, DESIGN.md §5); returns ``(final_state, outs)``.

    Runs under the host span ``trace_schedule`` (:mod:`repro.spans`).  Under
    ``jax.jit`` this body runs only when JAX traces, so in a profiler trace
    each such span is one (re)trace of the schedule.
    """
    if collect is not None and observables:
        raise ValueError("pass either collect= or observables=, not both")
    step_fn = (scheduler or Scheduler.default(config)).step

    obs = tuple(observables or ())
    names = [n for n, _, _ in obs]
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate observable names in {names}")
    streamed = tuple((n, f) for n, f, k in obs if k == 1)
    gated = tuple((n, f, k) for n, f, k in obs if k > 1)

    if gated:
        protos = jax.eval_shape(
            lambda s: {name: fn(s) for name, fn, _ in gated}, state
        )
        bufs0 = {
            name: jnp.zeros((-(-n_steps // k),) + protos[name].shape,
                            protos[name].dtype)
            for name, _, k in gated
        }
        idx0 = {name: jnp.zeros((), jnp.int32) for name, _, _ in gated}
    else:
        bufs0, idx0 = {}, {}

    def body(carry, _):
        st, bufs, idx = carry
        new = step_fn(st)
        bufs, idx = dict(bufs), dict(idx)
        for name, fn, k in gated:
            fires = (st.step % k) == 0
            row = idx[name]

            def write(b, _fn=fn, _row=row):
                return b.at[_row].set(_fn(new))

            bufs[name] = jax.lax.cond(fires, write, lambda b: b, bufs[name])
            idx[name] = row + fires.astype(jnp.int32)
        if streamed:
            out = {name: fn(new) for name, fn in streamed}
        elif collect is not None:
            out = collect(new)
        else:
            out = jnp.zeros((), jnp.int32)
        return (new, bufs, idx), out

    (final, bufs, _), outs = jax.lax.scan(
        body, (state, bufs0, idx0), None, length=n_steps
    )
    if gated:
        merged = dict(outs) if streamed else {}
        merged.update(bufs)
        outs = merged
    return final, outs


def jitted_runner(config: EngineConfig, scheduler: Optional[Scheduler] = None):
    """A reusable jitted runner for one (config, scheduler).

    Each :func:`run_jit` call builds a fresh ``jax.jit`` wrapper (whose
    trace cache dies with it — the right lifetime for one-shot runs like a
    PSO objective); callers that drive an evolving state in chunks should
    hold onto one of these instead so the compiled scan is reused —
    ``BuiltSimulation.run_jit`` does exactly that.
    """
    return jax.jit(
        functools.partial(run, config, scheduler=scheduler),
        static_argnames=("n_steps", "collect", "observables"),
    )


def run_jit(config: EngineConfig, state: SimulationState, n_steps: int,
            collect=None, scheduler: Optional[Scheduler] = None,
            observables=None):
    """Jitted entry point (config/n_steps/scheduler/observables static)."""
    fn = jitted_runner(config, scheduler)
    return fn(state, n_steps=n_steps, collect=collect, observables=observables)


# Convenience observables ---------------------------------------------------

def derive_n_kinds(kind: Array) -> int:
    """``max(kind) + 1`` from a concrete kind array — the single derivation
    used by every kind-count observable.  Raises under a trace (the count
    sizes an output array, so it must be static) and only spans kinds
    *currently present*."""
    if isinstance(kind, jax.core.Tracer):
        raise ValueError(
            "deriving n_kinds under jit/scan is impossible (the output "
            "shape must be static) — pass n_kinds= explicitly"
        )
    return int(jax.device_get(kind).max()) + 1 if kind.size else 1


def count_kinds(state, n_kinds: Optional[int] = None) -> Array:
    """Per-kind alive counts — the SIR observable of Fig 4.17.

    Flattens any leading device axis, so the same function serves
    ``SimulationState`` and the distributed engine's stacked ``DistState``.
    ``n_kinds`` defaults to :func:`derive_n_kinds` — but only outside
    jit/scan; under a trace pass it explicitly
    (``functools.partial(count_kinds, n_kinds=...)`` as a ``collect``), or
    use the :class:`~repro.core.api.Simulation` facade's kind-counts
    observable, which derives it from the registered agent groups at build
    time.  The derived default only spans kinds *currently present* — a
    model whose dynamics can reach higher kind values (e.g. SIR before
    anyone recovered) needs the explicit argument.
    """
    kind = state.pool.kind.reshape(-1)
    alive = state.pool.alive.reshape(-1)
    if n_kinds is None:
        n_kinds = derive_n_kinds(kind)
    onehot = (kind[:, None] == jnp.arange(n_kinds)[None, :]) & alive[:, None]
    return jnp.sum(onehot.astype(jnp.int32), axis=0)
