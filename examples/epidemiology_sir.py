"""Epidemiology use case (paper §4.6.3, Fig 4.17): agent-based SIR vs the
analytical Kermack–McKendrick solution, with PSO parameter calibration.

The paper validates BioDynaMo by showing the agent-based SIR curves match
the ODE solution for measles (R₀=12.9, T_R=8 d) after calibrating the
infection radius / probability / movement with particle swarm optimization.
This example reproduces that pipeline end to end:

  1. integrate dS/dt = −βSI/N, dI/dt = βSI/N − γI, dR/dt = γI  (RK4);
  2. run the agent-based model (random movement + infection + recovery,
     toroidal space) with candidate parameters;
  3. PSO over (infection_radius, infection_probability, max_movement)
     minimizing the mean-squared error of the S/I/R trajectories;
  4. report the final normalized error.

Model-API demo (DESIGN.md §6): the ABM is one declarative `Simulation` —
the S/I/R curves come from the built-in kind-counts observable (recorded
through the `lax.scan` ys, no hand-rolled `collect`), and the
`infectious_time` custom post op tracks each agent's infectious period.

Fault-tolerance demo (DESIGN.md §7): pass ``--checkpoint-dir`` to persist
the run every ``--checkpoint-every`` steps; rerunning with the same
directory resumes from the latest checkpoint instead of starting over, and
``--kill-at N`` SIGKILLs the process mid-run (after the first checkpoint at
step ≥ N) so CI can verify kill-and-resume reproduces the uninterrupted
observable series bit-for-bit.

Run:  python examples/epidemiology_sir.py [--fast] [--smoke]
"""

import argparse
import dataclasses
import hashlib
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np

from repro import Simulation
from repro.core import (
    INFECTED,
    RECOVERED,
    SUSCEPTIBLE,
    random_movement,
    sir_infection,
    sir_recovery,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import pso

# Measles (paper Table 4.3): R0 = 12.9, recovery duration 8 days.
BETA, GAMMA = 0.06719, 0.00521          # per hour, from R0=β/γ, γ=1/(8·24)


def infectious_time_op(ctx, state):
    """Custom standalone op: accumulate each agent's time spent infected."""
    pool = state.pool
    dt = jnp.where(pool.alive & (pool.kind == INFECTED), ctx.config.dt, 0.0)
    return dataclasses.replace(
        state, pool=pool.set_attr("t_inf", pool.get("t_inf") + dt)
    )


def analytical_sir(n: int, i0: int, beta: float, gamma: float, steps: int):
    """RK4 integration of the Kermack–McKendrick ODEs (hourly steps)."""
    y = np.array([n - i0, i0, 0.0], np.float64)

    def f(y):
        s, i, r = y
        inf = beta * s * i / n
        return np.array([-inf, inf - gamma * i, gamma * i])

    out = [y.copy()]
    for _ in range(steps):
        k1 = f(y)
        k2 = f(y + 0.5 * k1)
        k3 = f(y + 0.5 * k2)
        k4 = f(y + k3)
        y = y + (k1 + 2 * k2 + 2 * k3 + k4) / 6.0
        out.append(y.copy())
    return np.stack(out)           # (steps+1, 3)


def run_abm(params, n, i0, space, steps, seed=0, return_state=False,
            checkpoint_dir=None, checkpoint_every=None, kill_at=None):
    radius, prob, move = params
    key = jax.random.PRNGKey(seed)
    pos = jax.random.uniform(key, (n, 3), minval=0.0, maxval=space)
    kind = jnp.where(jnp.arange(n) < i0, INFECTED, SUSCEPTIBLE)
    sim = (
        Simulation(space=(0.0, space), cell_size=max(float(radius), 4.0),
                   boundary="toroidal", dt=1.0, max_per_cell=128, seed=seed)
        .add_agents(n, position=pos, diameter=0.5, kind=kind, t_inf=0.0)
        .use(
            random_movement(float(move)),
            sir_infection(float(radius), float(prob)),
            sir_recovery(GAMMA),
        )
        .op(infectious_time_op, name="infectious_time", phase="post")
        .observe_kinds("counts", n_kinds=3)   # S/I/R curves via the scan ys
    )
    if checkpoint_dir is None:
        final, obs = sim.run_jit(steps)
    else:
        from repro.checkpoint import latest_step

        on_chunk = None
        if kill_at is not None:
            def on_chunk(state):
                if int(np.asarray(state.step).ravel()[0]) >= kill_at:
                    os.kill(os.getpid(), signal.SIGKILL)

        if latest_step(checkpoint_dir) is not None:
            final, obs = sim.resume(checkpoint_dir, on_chunk=on_chunk)
        else:
            final, obs = sim.run_jit(
                steps, checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every, on_chunk=on_chunk)
    counts = np.asarray(obs["counts"])       # (steps, 3)
    if return_state:
        return counts, final
    return counts


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="small population, no PSO")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny run for CI: build + step, skip the science bar")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="persist the run here; rerun resumes from latest")
    ap.add_argument("--checkpoint-every", type=int, default=3,
                    help="steps between checkpoints (with --checkpoint-dir)")
    ap.add_argument("--kill-at", type=int, default=None,
                    help="SIGKILL after the first checkpoint at step >= N "
                         "(CI kill-and-resume smoke)")
    args = ap.parse_args(argv)
    if args.kill_at is not None and args.checkpoint_dir is None:
        ap.error("--kill-at requires --checkpoint-dir")

    if args.smoke:
        counts, final = run_abm((3.24, 0.36, 6.2), 150, 6, 40.0, 10,
                                return_state=True,
                                checkpoint_dir=args.checkpoint_dir,
                                checkpoint_every=args.checkpoint_every,
                                kill_at=args.kill_at)
        assert counts.shape == (10, 3) and (counts.sum(axis=1) == 150).all()
        assert float(np.asarray(final.pool.get("t_inf")).max()) > 0.0
        digest = hashlib.sha256(np.ascontiguousarray(counts).tobytes())
        print(f"counts sha256={digest.hexdigest()}")
        print("smoke run OK (facade model built + stepped, counts recorded)")
        return 0.0

    n, i0, space = (400, 8, 55.0) if args.fast else (2000, 20, 100.0)
    steps = 300 if args.fast else 1000

    truth = analytical_sir(n, i0, BETA, GAMMA, steps)[1:]

    def objective(p):
        sim = run_abm(p, n, i0, space, steps)
        return float(np.mean(((sim - truth) / n) ** 2))

    if args.fast:
        # Paper Table-4.3 measles radius; probability/movement recalibrated
        # (PSO-style sweep) for the fast-mode density — the published triple
        # (3.24, 0.285, 5.79) was calibrated at n=2000/space=100 and spreads
        # too slowly at n=400/space=55 (rmse 0.090 vs the 0.08 bar).
        best = np.array([3.24, 0.36, 6.2])
        err = objective(best)
        print(f"fixed calibrated parameters: normalized MSE {err:.5f}")
    else:
        best, err, hist = pso.optimize(
            objective,
            bounds=[(1.0, 6.0), (0.05, 0.6), (1.0, 8.0)],
            n_iters=8,
            config=pso.PSOConfig(n_particles=8, seed=1),
            verbose=True,
        )
        print(f"PSO best: radius={best[0]:.3f} prob={best[1]:.3f} "
              f"move={best[2]:.3f} → MSE {err:.5f}")

    sim, final = run_abm(best, n, i0, space, steps, return_state=True)
    rmse = np.sqrt(np.mean(((sim - truth) / n) ** 2))
    peak_ana = truth[:, 1].max() / n
    peak_sim = sim[:, 1].max() / n
    # Custom-op observable: mean infectious period of completed episodes.
    t_inf = np.asarray(final.pool.get("t_inf"))
    recovered = np.asarray(final.pool.kind) == RECOVERED
    if recovered.any():
        print(f"mean infectious period (custom op): "
              f"{t_inf[recovered].mean():.0f} h (ODE 1/γ = {1/GAMMA:.0f} h)")
    print(f"epidemic peak: analytical {peak_ana:.3f}, agent-based {peak_sim:.3f}")
    print(f"trajectory RMSE (fraction of population): {rmse:.4f}")
    assert rmse < 0.08, "agent-based model does not match the analytical SIR"
    print("agent-based SIR matches the analytical solution ✓ (cf. Fig 4.17)")
    return rmse


if __name__ == "__main__":
    enable_compile_cache()
    main()
