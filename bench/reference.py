"""Plain reference of the simulation semantics the benchmark checks.

Straightforward ``jax.numpy`` over host-given arrays, written from the model
definitions (BioDynaMo's uniform grid, Eq 4.1 contact forces, Eq 4.3
diffusion, Algorithms 3-7) and not from the program under test: nothing here
imports the program, and nothing takes a table, index or key the program made
other than the state it is handed.  Every function takes ``dtype`` so that the
same arithmetic in a lower precision serves as the control of the comparison.

Neighbour search is a cell-sorted sweep with no capacity per cell: the agents
are ordered by cell with a stable argsort, and each query visits every agent
of its 27 neighbouring cells (``fori_loop`` up to the fullest cell's count),
so no pair is ever truncated.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

OFFSETS = np.array(
    [(dx, dy, dz) for dx in (-1, 0, 1) for dy in (-1, 0, 1) for dz in (-1, 0, 1)],
    np.int32,
)


def grid_dims(lo: float, hi: float, cell: float) -> tuple[int, float]:
    """Cells per side and box edge of a cubic space cut into boxes of at
    least ``cell`` (the largest whole number of them that fits)."""
    n = max(int((hi - lo) / cell), 1)
    return n, (hi - lo) / n


def cell_coords(pos, lo: float, box: float, n: int):
    """(N, 3) positions -> (N, 3) int32 box coordinates, clipped to the grid."""
    ijk = jnp.floor((pos.astype(jnp.float32) - lo) / jnp.float32(box))
    return jnp.clip(ijk.astype(jnp.int32), 0, n - 1)


def morton_code(ijk):
    """Z-order code of (N, 3) box coordinates: bit b of x, y, z lands at bit
    3b, 3b + 1, 3b + 2 (10 bits per axis)."""
    ijk = ijk.astype(jnp.uint32)
    code = jnp.zeros(ijk.shape[:-1], jnp.uint32)
    for b in range(10):
        for axis in range(3):
            bit = (ijk[..., axis] >> b) & jnp.uint32(1)
            code = code | (bit << jnp.uint32(3 * b + axis))
    return code


def layout_order(pos, alive, lo: float, box: float, n: int):
    """Slot order after the layout sort: live agents by the Z-order code of
    their box (ties in slot order), dead agents last."""
    key = morton_code(cell_coords(pos, lo, box, n))
    key = jnp.where(alive, key, jnp.uint32(0xFFFFFFFF))
    return jnp.argsort(key, stable=True)


class Neighbours:
    """Agents grouped by box, for 27-box sweeps."""

    def __init__(self, ijk, alive, n: int):
        lin = (ijk[:, 0] * n + ijk[:, 1]) * n + ijk[:, 2]
        lin = jnp.where(alive, lin, n ** 3)
        self.order = jnp.argsort(lin, stable=True).astype(jnp.int32)
        counts = jnp.zeros((n ** 3 + 1,), jnp.int32).at[lin].add(1)
        self.counts = counts[: n ** 3]
        self.starts = (jnp.cumsum(counts) - counts)[: n ** 3]
        self.kmax = int(jax.device_get(jnp.max(self.counts)))
        self.n = n

    def sweep(self, visit, query_ijk, init, *operands, wrap: bool = False):
        """Fold ``visit(acc, j, valid, *operands)`` over every (query,
        source) pair whose boxes are adjacent, across the faces too where
        ``wrap`` (a torus); ``j`` holds one source id per query."""
        if wrap and self.n < 3:
            raise ValueError("a wrapped sweep needs 3 boxes or more per side")
        return _sweep(visit, self.n, wrap, query_ijk, self.order,
                      self.counts, self.starts, jnp.int32(self.kmax), init,
                      operands)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _sweep(visit, n, wrap, query_ijk, order, counts, starts, kmax, init,
           operands):
    offsets = jnp.asarray(OFFSETS)
    m = order.shape[0]

    def body(t, acc):
        o, j = t // kmax, t % kmax
        nb = query_ijk + offsets[o]
        if wrap:
            nb = jnp.mod(nb, n)
        inside = jnp.all((nb >= 0) & (nb < n), axis=-1)
        nb = jnp.clip(nb, 0, n - 1)
        cid = (nb[:, 0] * n + nb[:, 1]) * n + nb[:, 2]
        valid = inside & (j < counts[cid])
        src = order[jnp.clip(starts[cid] + j, 0, m - 1)]
        return visit(acc, src, valid, *operands)

    return jax.lax.fori_loop(0, 27 * kmax, body, init)


def _force_visit(acc, j, valid, pos, radius, alive, k, gamma):
    ids = jnp.arange(pos.shape[0], dtype=jnp.int32)
    dx = pos - pos[j]
    dist = jnp.sqrt(jnp.sum(dx * dx, axis=-1))
    r1, r2 = radius, radius[j]
    delta = r1 + r2 - dist
    rbar = r1 * r2 / (r1 + r2)
    mag = k * delta - gamma * jnp.sqrt(jnp.maximum(rbar * delta, 0))
    # Coincident centres have no direction: no force (as in the program).
    use = valid & alive & (j != ids) & (delta > 0) & (dist > 0)
    f = mag[:, None] * dx / jnp.where(use, dist, 1)[:, None]
    return acc + jnp.where(use[:, None], f, 0).astype(acc.dtype)


def contact_forces(pos, radius, alive, build_ijk, n: int, k: float,
                   gamma: float, dtype):
    """Eq 4.1 net force on every agent from every other agent whose box at
    build time is adjacent to its own: F = k*d - gamma*sqrt(rbar*d) along
    the centre line when the spheres overlap (d > 0)."""
    pos = pos.astype(dtype)
    nb = Neighbours(build_ijk, alive, n)
    return nb.sweep(_force_visit, build_ijk, jnp.zeros_like(pos), pos,
                    radius.astype(dtype), alive, jnp.asarray(k, dtype),
                    jnp.asarray(gamma, dtype))


def _close_visit(acc, j, valid, query_pos, src_pos, src_flag, r2, edge):
    ids = jnp.arange(query_pos.shape[0], dtype=jnp.int32)
    d = query_pos - src_pos[j]
    # On a torus (edge > 0) the distance is to the nearest image.
    d = jnp.where(edge > 0, d - edge * jnp.round(d / jnp.where(edge > 0, edge, 1)), d)
    close = jnp.sum(d * d, axis=-1) <= r2
    return acc | (valid & (j != ids) & src_flag[j] & close)


def any_close(query_pos, src_pos, src_flag, alive, lo: float, hi: float,
              n: int, radius: float, dtype, torus: bool,
              query_ijk=None):
    """Per query agent: does another live agent flagged ``src_flag`` lie
    within ``radius`` of the query's position?  Sources stand at
    ``src_pos``; on a torus the distance is to the nearest image.  The
    sweep visits the boxes around the query's own box, which cover the
    radius when a box is at least ``radius`` wide; ``query_ijk`` replaces
    that box (the witness of a search that starts elsewhere)."""
    box = (hi - lo) / n
    if radius > box:
        raise ValueError(f"radius {radius} exceeds the box {box}")
    src_ijk = cell_coords(src_pos, lo, box, n)
    if query_ijk is None:
        q = jnp.floor((query_pos.astype(jnp.float32) - lo) / jnp.float32(box))
        q = q.astype(jnp.int32)
        query_ijk = jnp.mod(q, n) if torus else jnp.clip(q, 0, n - 1)
    nb = Neighbours(src_ijk, alive, n)
    edge = jnp.asarray(hi - lo if torus else 0.0, dtype)
    return nb.sweep(_close_visit, query_ijk,
                    jnp.zeros(query_pos.shape[0], bool),
                    query_pos.astype(dtype), src_pos.astype(dtype),
                    src_flag & alive, jnp.asarray(radius, dtype) ** 2, edge,
                    wrap=torus)


# ------------------------------------------------------------- substances

def nearest_voxel(pos, origin: float, spacing: float, res: int):
    rel = (pos - origin) / spacing - 0.5
    return jnp.clip(jnp.round(rel).astype(jnp.int32), 0, res - 1)


def secrete(conc, pos, mask, amount: float, origin, spacing, res):
    v = nearest_voxel(pos, origin, spacing, res)
    add = jnp.where(mask, jnp.asarray(amount, conc.dtype), 0).astype(conc.dtype)
    return conc.at[v[:, 0], v[:, 1], v[:, 2]].add(add)


def sample(conc, pos, origin, spacing, res):
    v = nearest_voxel(pos, origin, spacing, res)
    return conc[v[:, 0], v[:, 1], v[:, 2]]


def unit_gradient(conc, pos, origin, spacing, res):
    """Central-difference gradient at each agent's voxel (clamped at the
    edges), scaled to unit length; zero where it vanishes."""
    v = nearest_voxel(pos, origin, spacing, res)

    def at(off):
        q = jnp.clip(v + jnp.asarray(off, jnp.int32), 0, res - 1)
        return conc[q[:, 0], q[:, 1], q[:, 2]]

    two_dx = 2 * jnp.asarray(spacing, conc.dtype)
    g = jnp.stack([
        (at((1, 0, 0)) - at((-1, 0, 0))) / two_dx,
        (at((0, 1, 0)) - at((0, -1, 0))) / two_dx,
        (at((0, 0, 1)) - at((0, 0, -1))) / two_dx,
    ], axis=-1)
    norm = jnp.sqrt(jnp.sum(g * g, axis=-1, keepdims=True))
    return jnp.where(norm > 1e-12, g / jnp.where(norm > 1e-12, norm, 1), 0)


def diffuse(conc, diffusion: float, decay: float, dt: float, spacing: float):
    """One explicit step of du/dt = D*lap(u) - decay*u, zero outside."""
    z = jnp.pad(conc, 1)
    lap = (z[2:, 1:-1, 1:-1] + z[:-2, 1:-1, 1:-1] + z[1:-1, 2:, 1:-1]
           + z[1:-1, :-2, 1:-1] + z[1:-1, 1:-1, 2:] + z[1:-1, 1:-1, :-2]
           - 6 * conc) / (spacing * spacing)
    return (conc * (1 - decay * dt) + diffusion * dt * lap).astype(conc.dtype)


# ------------------------------------------------------------- randomness

def step_key(rng, step: int):
    """The key of one iteration: the run's key folded with the step."""
    return jax.random.fold_in(jnp.asarray(rng, jnp.uint32), step)


def next_key(key):
    """(carry, use) = split(key): each stochastic behaviour draws from the
    second half and hands the first on."""
    carry, use = jax.random.split(key)
    return carry, use
