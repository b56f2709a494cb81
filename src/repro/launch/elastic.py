"""Fault tolerance + elastic scaling policy (DESIGN.md §7).

This module encodes the cluster-operations contract the ABM runtime is built
around.  On this single-host container the mechanisms are exercised by tests
(tests/test_checkpoint.py resume-equivalence, tests/test_faults.py
fault-injection) and the CI kill-and-resume smoke; on a real cluster the same
functions drive the coordinator.

Failure model & responses
-------------------------
1. **Process/host death mid-run** — ``Simulation.run(...,
   checkpoint_dir=)`` persists the full run pytree (state + observable rows)
   atomically every interval; the step is a pure function over that state,
   so ``Simulation.resume(dir)`` finishes the run *bit-exactly* (per-step
   RNG folds the absolute step counter — chunks compose into one long scan).
   A crash mid-write leaves a ``.tmp_ckpt_*`` directory the loader never
   sees; a corrupted payload invalidates that step and resume degrades to
   the previous interval (checkpoint/checkpoint.py).
2. **Capacity saturation** — pools, migration buffers, and halo buffers are
   fixed-capacity (XLA static shapes); saturation sets counters instead of
   corrupting the step (pool.overflow, migrate/halo_overflow,
   GridIndex.overflowed), folded into ``state.health`` by the scheduler's
   health op.  :func:`check_abm_state` turns a host-side read of that report
   into an :class:`ElasticAction`; :func:`run_elastic` /
   :func:`run_elastic_distributed` respond by restoring the latest
   checkpoint into ``grow_factor``×-larger pools (:func:`grow_state` /
   :func:`grow_dist_state` — surviving agents bit-identical modulo dead
   padding) and replaying the saturated chunk.  Cell-list overflow is *not*
   a regrow trigger: the engine's dense fallback keeps physics bit-exact,
   so it is a performance signal only.
3. **Numerical corruption** — non-finite positions/attrs (model bug, dt too
   large) trip ``health.nonfinite_agents``; growing cannot fix NaNs, so the
   policy halts with the counts named rather than burning a regrow budget.
4. **Host failure under a mesh (LM-era path, kept)** — the coordinator
   rebuilds the largest surviving power-of-two mesh
   (:func:`surviving_mesh_shape`) and re-shards the latest checkpoint onto
   it (:func:`reshard_plan`).

Detection is pure and jit-safe (the health op runs inside the scan); policy
runs host-side between chunks — this module deliberately imports no jax at
module scope so the policy layer stays importable anywhere.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class ElasticAction:
    kind: str          # "continue" | "grow_capacity" | "halt" | "rebuild_mesh"
    reason: str = ""
    grow_factor: float = 1.0


def _count(health, name: str) -> int:
    return int(np.asarray(getattr(health, name, 0)).sum())


def check_abm_state(health, grow_factor: float = 2.0) -> ElasticAction:
    """Turn a host-side read of the health report into a policy decision.

    Duck-typed: anything carrying the
    :class:`~repro.core.schedule.HealthReport` counter attributes works — a
    per-device stacked report sums across devices, and missing attributes
    read as zero.  Priorities: non-finite agent state halts (regrowing
    cannot fix NaNs); any saturation counter asks for a capacity regrow;
    cell-list overflow alone continues (the dense fallback already kept the
    step bit-exact).
    """
    nonfinite = _count(health, "nonfinite_agents")
    if nonfinite > 0:
        return ElasticAction(
            "halt",
            f"{nonfinite} agents with non-finite state across "
            f"{_count(health, 'nonfinite_steps')} flagged steps — growing "
            f"capacity cannot fix numerical corruption",
        )
    pool = _count(health, "pool_overflow")
    if pool > 0:
        return ElasticAction(
            "grow_capacity", f"agent pool overflowed by {pool}", grow_factor
        )
    mig = _count(health, "migrate_overflow")
    halo = _count(health, "halo_overflow")
    if mig > 0 or halo > 0:
        return ElasticAction(
            "grow_capacity",
            f"exchange buffers overflowed (migrate {mig}, halo {halo})",
            grow_factor,
        )
    return ElasticAction("continue")


# ---------------------------------------------------------------------------
# Regrowth: restore a checkpoint into larger pools
# ---------------------------------------------------------------------------


def grow_pool(pool, new_capacity: int, axis: int = 0):
    """Pad the pool's agent axis to ``new_capacity`` with dead slots.

    Surviving-agent rows are bit-identical; padding matches ``make_pool``'s
    (zero values, ``alive=False``).  ``overflow`` resets — it counted drops
    against the old capacity.  ``axis=1`` serves the distributed stacked
    pool (leading device axis).
    """
    import jax.numpy as jnp

    old = pool.position.shape[axis]
    if new_capacity < old:
        raise ValueError(f"cannot shrink pool capacity {old} → {new_capacity}")
    pad = new_capacity - old

    def _pad(x):
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        return jnp.pad(x, widths)

    return pool.replace(
        position=_pad(pool.position),
        diameter=_pad(pool.diameter),
        kind=_pad(pool.kind),
        age=_pad(pool.age),
        alive=_pad(pool.alive),
        static=_pad(pool.static),
        attrs={k: _pad(v) for k, v in pool.attrs.items()},
        overflow=jnp.zeros_like(pool.overflow),
    )


def grow_state(state, new_capacity: int):
    """Single-node regrow: pool padded to ``new_capacity``, health report
    reset (it described the saturated run being rolled back)."""
    from repro.core.schedule import empty_health

    return dataclasses.replace(
        state,
        pool=grow_pool(state.pool, new_capacity, axis=0),
        health=empty_health(),
    )


def grow_dist_state(state, new_capacity: int, new_dcfg):
    """Distributed regrow: per-device pool rows padded to ``new_capacity``,
    fresh halo-codec buffers at the new halo capacity (the codec's
    ``prev_ids`` freshness bits make a reset safe — the first post-regrow
    exchange ships full precision), exchange counters and health reset.
    Cumulative wire-byte accounting is preserved."""
    import jax
    import jax.numpy as jnp

    from repro.core.distributed import GhostFrame, HaloCodecState
    from repro.core.schedule import empty_health

    n_dev = state.pool.position.shape[0]
    scale = np.asarray(jax.device_get(state.codec.scale))[0]
    codec1 = HaloCodecState.create(
        new_dcfg.n_decomposed, new_dcfg.halo_capacity, scale
    )
    stack = lambda tree: jax.tree.map(
        lambda x: jnp.stack([x] * n_dev), tree
    )
    zeros = jnp.zeros((n_dev,), jnp.int32)
    return dataclasses.replace(
        state,
        pool=grow_pool(state.pool, new_capacity, axis=1),
        codec=stack(codec1),
        migrate_overflow=zeros,
        halo_overflow=zeros,
        health=stack(empty_health()),
        # The aura double buffer sizes with halo_capacity; a zeroed frame is
        # safe — every step's exchange rewrites it before any op reads it.
        ghost=stack(GhostFrame.create(new_dcfg)),
    )


# ---------------------------------------------------------------------------
# Elastic drivers: run → inspect health → (commit | regrow-and-replay)
# ---------------------------------------------------------------------------


def _obs_like(acc: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    return {k: np.asarray(v) for k, v in acc.items()}


def run_elastic(
    sim,
    n_steps: int,
    checkpoint_dir: str,
    checkpoint_every: Optional[int] = None,
    grow_factor: float = 2.0,
    max_regrows: int = 3,
    jit: bool = True,
    seed: Optional[int] = None,
    keep: int = 3,
):
    """Saturation-driven elastic run on the single-node engine.

    Runs in ``checkpoint_every``-step chunks.  After each chunk the health
    report is read host-side; on saturation the chunk is *not* committed —
    the latest checkpoint (written before it) is restored, the facade is
    rebuilt with ``capacity = ⌈grow_factor × old⌉``, the restored state is
    padded into the bigger pool (:func:`grow_state`), and the chunk
    replays.  Returns ``(final_state, {name: rows}, n_regrows)``; raises
    ``RuntimeError`` on a halt action or when ``max_regrows`` is exhausted.
    """
    import jax
    import jax.numpy as jnp

    from repro import checkpoint as ckpt
    from repro.core.api import _concat_obs, _step_of

    built = sim.build(seed=seed)
    every = int(checkpoint_every) if checkpoint_every else int(n_steps)
    if every <= 0:
        raise ValueError(f"checkpoint_every must be positive, got {every}")
    state = built.state
    acc: Dict[str, np.ndarray] = {}
    target = _step_of(state) + int(n_steps)
    grows = 0

    def save(st):
        ckpt.save(checkpoint_dir, _step_of(st), {"state": st, "obs": acc},
                  keep=keep)

    save(state)
    while _step_of(state) < target:
        chunk = min(every, target - _step_of(state))
        runner = built.run_jit if jit else built.run
        new_state, obs = runner(chunk, state=state)
        action = check_abm_state(jax.device_get(new_state.health), grow_factor)
        if action.kind == "halt":
            raise RuntimeError(
                f"elastic run halted at step {_step_of(new_state)}: "
                f"{action.reason}"
            )
        if action.kind == "grow_capacity":
            if grows >= max_regrows:
                raise RuntimeError(
                    f"still saturated after {grows} regrows: {action.reason}"
                )
            grows += 1
            old_cap = state.pool.position.shape[0]
            new_cap = int(np.ceil(old_cap * action.grow_factor))
            _, payload = ckpt.restore(
                checkpoint_dir, {"state": state, "obs": _obs_like(acc)}
            )
            restored = jax.tree.map(jnp.asarray, payload["state"])
            sim.capacity = new_cap
            built = sim.build(seed=seed)
            state = grow_state(restored, new_cap)
            save(state)                    # re-anchor at the new capacity
            continue                       # replay the chunk, bigger pool
        state = new_state
        acc = _concat_obs(acc, obs)
        save(state)
    return state, {k: jnp.asarray(v) for k, v in acc.items()}, grows


def run_elastic_distributed(
    sim,
    mesh,
    dcfg,
    n_steps: int,
    checkpoint_dir: str,
    checkpoint_every: Optional[int] = None,
    grow_factor: float = 2.0,
    max_regrows: int = 3,
    seed: Optional[int] = None,
    keep: int = 3,
    capacity: Optional[int] = None,
):
    """Distributed counterpart of :func:`run_elastic`.

    A regrow scales the per-device pool capacity AND the exchange-buffer
    bounds (``halo_capacity`` / ``migrate_capacity``) by ``grow_factor``,
    re-deploys via ``sim.distribute`` on the grown
    :class:`~repro.core.distributed.DomainConfig`, and pads the restored
    state into the new shapes (:func:`grow_dist_state`).  Returns
    ``(final_state, {name: rows}, n_regrows)``.
    """
    import jax
    import jax.numpy as jnp

    from repro import checkpoint as ckpt
    from repro.core.api import _concat_obs, _step_of

    dsim = sim.distribute(mesh, dcfg, capacity=capacity, seed=seed)
    every = int(checkpoint_every) if checkpoint_every else int(n_steps)
    if every <= 0:
        raise ValueError(f"checkpoint_every must be positive, got {every}")
    state = dsim.state
    acc: Dict[str, np.ndarray] = {}
    target = _step_of(state) + int(n_steps)
    grows = 0

    def save(st):
        ckpt.save(checkpoint_dir, _step_of(st), {"state": st, "obs": acc},
                  keep=keep)

    save(state)
    while _step_of(state) < target:
        chunk = min(every, target - _step_of(state))
        new_state, obs = dsim.run(chunk, state=state)
        action = check_abm_state(jax.device_get(new_state.health), grow_factor)
        if action.kind == "halt":
            raise RuntimeError(
                f"elastic run halted at step {_step_of(new_state)}: "
                f"{action.reason}"
            )
        if action.kind == "grow_capacity":
            if grows >= max_regrows:
                raise RuntimeError(
                    f"still saturated after {grows} regrows: {action.reason}"
                )
            grows += 1
            g = action.grow_factor
            old_cap = state.pool.position.shape[1]
            new_cap = int(np.ceil(old_cap * g))
            dcfg = dataclasses.replace(
                dcfg,
                halo_capacity=int(np.ceil(dcfg.halo_capacity * g)),
                migrate_capacity=int(np.ceil(dcfg.migrate_capacity * g)),
            )
            _, payload = ckpt.restore(
                checkpoint_dir, {"state": state, "obs": _obs_like(acc)}
            )
            restored = jax.tree.map(jnp.asarray, payload["state"])
            dsim = sim.distribute(mesh, dcfg, capacity=new_cap, seed=seed)
            state = grow_dist_state(restored, new_cap, dcfg)
            save(state)                    # re-anchor at the new shapes
            continue
        state = new_state
        acc = _concat_obs(acc, obs)
        save(state)
    return state, {k: jnp.asarray(v) for k, v in acc.items()}, grows


# ---------------------------------------------------------------------------
# Mesh survival (LM-era host-failure path, kept for the coordinator)
# ---------------------------------------------------------------------------


def surviving_mesh_shape(n_healthy_hosts: int, devices_per_host: int,
                         model_parallel: int) -> Optional[Tuple[int, int]]:
    """Largest (data, model) mesh fitting the surviving devices.

    Keeps the model axis fixed (TP degree is a property of the model
    sharding) and shrinks the data axis to the largest power of two that
    fits — the checkpoint re-shards onto it (params are sharded over
    (data, model); shrinking data only changes the FSDP factor)."""
    total = n_healthy_hosts * devices_per_host
    if total < model_parallel:
        return None
    data = 1 << int(np.log2(total // model_parallel))
    return (data, model_parallel)


def reshard_plan(old_shape: Tuple[int, int], new_shape: Tuple[int, int]) -> str:
    """Human-readable plan for re-sharding a checkpoint across mesh sizes.

    npz checkpoints store full (unsharded) arrays, so re-sharding is just
    loading with the new mesh's NamedShardings; at exascale one would store
    sharded array files + an index and do a shuffle read — the manifest
    format (checkpoint/checkpoint.py) leaves room for per-shard entries."""
    return (
        f"restore full arrays from latest manifest; "
        f"device_put with NamedShardings of mesh {new_shape} "
        f"(was {old_shape}); data-axis batch size rescales by "
        f"{new_shape[0] / old_shape[0]:.2f}×, lr rescaled accordingly"
    )
