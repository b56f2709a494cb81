"""Pallas TPU kernel: fused cell-list contact forces (Eq 4.1, §5.6.3).

The `pairwise_force` kernel fuses the force *arithmetic* but still consumes
the dense ``(N, 27·M)`` candidate tensor and its ``(N, K, 3)`` XLA gather —
tens of HBM bytes per force FLOP.  This kernel removes the candidate stage
entirely by walking the grid's cell list directly, carrying the BioDynaMo /
PhysiCell insight (neighbor *data movement*, not FLOPs, limits the force
pass — arXiv:2301.06984, arXiv:2306.11544) into the Pallas layer:

  * agents live in **cell-major, component-planar slots**: position/radius/
    occupancy are laid out as ``(·, n_cols, nz, M)`` where a *column* is one
    (x, y) stack of nz cells and M = max_per_cell.  This is the §5.4.2
    "SoA + sorted" layout — the grid build *is* the sort, so slot order is
    spatial order and every block load below is a contiguous DMA.
  * grid = ``(n_cols, 9)``: one program per (column, (dx, dy) offset).  The
    neighbor column for offset (dx, dy) sits at a *block-aligned* shift of
    ``dx·ny + dy`` columns, so its BlockSpec index map is plain arithmetic on
    grid indices — no scatter/gather, no candidate ids.
  * the dz ∈ {−1, 0, +1} stencil leg is an **intra-block static shift** of
    the loaded neighbor column (cells are z-contiguous inside a column), so
    the full 27-box neighborhood costs 9 column loads, not 27.
  * forces accumulate in the VMEM output block across the 9-offset inner
    grid axis (same revisiting pattern as `pairwise_force`); per-pair
    intermediates (dist/δ/r̄/magnitude) never leave VMEM.

Boundary cells are handled by masking, not halos-of-data: columns are padded
with ``ny+1`` empty ghost columns per side so shifted loads stay in range,
and a per-program scalar test on the decoded (x, y) kills out-of-grid
offsets (including the row-major wrap-around a linear shift would otherwise
alias to the wrong cell).  Self-interaction is the (i == j) diagonal of the
center offset at dz = 0 — one static mask, no id comparison.

Validated in interpret mode against ref.py on the CPU; on a TPU the same
code lowers through Mosaic (tests/test_tpu_compile.py compiles it for a
described v5e).  VMEM per program is O(nz·M) block rows plus O(nz·M²)
pair temporaries.

Distributed adoption (§6.2.1, DESIGN.md §4): the kernel is oblivious to the
local/ghost split — the distributed engine builds the cell list over its
halo-*extended* grid (halo agents land in boundary cells, so the column
decomposition and the 9-offset shift arithmetic apply unchanged) and
gathers only local rows back in ops.py (``num_out``).  Ghost
slots cost kernel FLOPs but no extra HBM layout: they are ordinary occupied
slots of boundary columns.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_default

Array = jax.Array


def _shift_z(x: Array, dz: int) -> Array:
    """Static shift along the leading (cell-z) axis: out[k] = x[k + dz].

    Rows shifted in from outside are garbage (wrapped) and must be masked by
    the caller's z-validity mask; static slices keep this Mosaic-lowerable.
    """
    if dz == 0:
        return x
    return jnp.concatenate([x[dz:], x[:dz]], axis=0)


def _cell_force_kernel(
    qpos_ref,      # (3, 1, nz, M) query column positions (component-planar)
    qrad_ref,      # (1, 1, nz, M)
    qval_ref,      # (1, 1, nz, M) int8 slot occupancy
    npos_ref,      # (3, 1, nz, M) neighbor column for this (dx, dy) offset
    nrad_ref,      # (1, 1, nz, M)
    nval_ref,      # (1, 1, nz, M)
    out_ref,       # (3, 1, nz, M) accumulated force
    *,
    nx: int,
    ny: int,
    nz: int,
    m: int,
    k: float,
    gamma: float,
):
    col = pl.program_id(0)
    off = pl.program_id(1)

    @pl.when(off == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # Decode the program's (x, y) column and the (dx, dy) offset; kill
    # offsets that leave the grid (also guards the ghost-column loads and
    # the row-major wrap-around of the linear column shift).
    x = col // ny
    y = col % ny
    dx_off = off // 3 - 1
    dy_off = off % 3 - 1
    xy_ok = (
        (x + dx_off >= 0) & (x + dx_off < nx)
        & (y + dy_off >= 0) & (y + dy_off < ny)
    )

    qx = qpos_ref[0, 0]                       # (nz, M)
    qy = qpos_ref[1, 0]
    qz = qpos_ref[2, 0]
    qr = qrad_ref[0, 0]
    qv = qval_ref[0, 0].astype(jnp.float32)

    npx = npos_ref[0, 0]
    npy = npos_ref[1, 0]
    npz = npos_ref[2, 0]
    nr = nrad_ref[0, 0]
    nv = nval_ref[0, 0].astype(jnp.float32)

    # Masks are built by comparing f32/int32 operands after they are
    # broadcast: Mosaic cannot reshape boolean vectors (no i1 shape casts).
    zs = jax.lax.broadcasted_iota(jnp.int32, (nz, 1, 1), 0)
    diag = (jax.lax.broadcasted_iota(jnp.int32, (1, m, m), 1)
            == jax.lax.broadcasted_iota(jnp.int32, (1, m, m), 2))  # self slot
    is_center = off == 4                       # dx = dy = 0
    q_ok = qv[:, :, None] > 0.0                # (nz, M, 1) → query occupied

    acc_x = jnp.zeros((nz, m), jnp.float32)
    acc_y = jnp.zeros((nz, m), jnp.float32)
    acc_z = jnp.zeros((nz, m), jnp.float32)

    for dz in (-1, 0, 1):                      # static: unrolled in-kernel
        sx = _shift_z(npx, dz)[:, None, :]     # (nz, 1, M) neighbor cell z+dz
        sy = _shift_z(npy, dz)[:, None, :]
        sz = _shift_z(npz, dz)[:, None, :]
        sr = _shift_z(nr, dz)[:, None, :]
        sv = _shift_z(nv, dz)[:, None, :]

        pair = q_ok & (sv > 0.0) & (zs + dz >= 0) & (zs + dz < nz) & xy_ok
        if dz == 0:
            # Self-pair: same cell, same slot — only at the center offset.
            pair = pair & ~(diag & is_center)

        dxc = qx[:, :, None] - sx              # (nz, M, M)
        dyc = qy[:, :, None] - sy
        dzc = qz[:, :, None] - sz
        dist = jnp.sqrt(dxc * dxc + dyc * dyc + dzc * dzc + 1e-20)
        delta = qr[:, :, None] + sr - dist
        overlap = (delta > 0.0) & pair
        rbar = qr[:, :, None] * sr / jnp.maximum(qr[:, :, None] + sr, 1e-20)
        mag = k * delta - gamma * jnp.sqrt(jnp.maximum(rbar * delta, 0.0))
        scale = jnp.where(overlap, mag / dist, 0.0)

        acc_x += jnp.sum(scale * dxc, axis=2)
        acc_y += jnp.sum(scale * dyc, axis=2)
        acc_z += jnp.sum(scale * dzc, axis=2)

    out_ref[...] += jnp.stack([acc_x, acc_y, acc_z], axis=0)[:, None]


@functools.partial(
    jax.jit, static_argnames=("dims", "k", "gamma", "interpret")
)
def cell_list_force_planar(
    cpos: Array,    # (3, n_cols + 2·pad, nz, M) f32 cell-major positions
    crad: Array,    # (1, n_cols + 2·pad, nz, M) f32
    cval: Array,    # (1, n_cols + 2·pad, nz, M) int8 occupancy
    dims: tuple,    # (nx, ny, nz) static grid dims
    k: float = 2.0,
    gamma: float = 1.0,
    interpret: bool | None = None,
) -> Array:
    """Per-slot net force, (3, n_cols, nz, M).

    Inputs carry ``pad = ny + 1`` ghost (empty) columns on each side of the
    column axis so every shifted neighbor load is in range.
    """
    nx, ny, nz = dims
    n_cols = nx * ny
    m = cpos.shape[-1]
    pad = ny + 1
    assert cpos.shape == (3, n_cols + 2 * pad, nz, m), (cpos.shape, dims)

    def nbr_idx(i, o):
        return (0, i + pad + (o // 3 - 1) * ny + (o % 3 - 1), 0, 0)

    def qry_idx(i, o):
        return (0, i + pad, 0, 0)

    kernel = functools.partial(
        _cell_force_kernel, nx=nx, ny=ny, nz=nz, m=m, k=k, gamma=gamma
    )
    return pl.pallas_call(
        kernel,
        grid=(n_cols, 9),
        in_specs=[
            pl.BlockSpec((3, 1, nz, m), qry_idx),
            pl.BlockSpec((1, 1, nz, m), qry_idx),
            pl.BlockSpec((1, 1, nz, m), qry_idx),
            pl.BlockSpec((3, 1, nz, m), nbr_idx),
            pl.BlockSpec((1, 1, nz, m), nbr_idx),
            pl.BlockSpec((1, 1, nz, m), nbr_idx),
        ],
        out_specs=pl.BlockSpec((3, 1, nz, m), lambda i, o: (0, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((3, n_cols, nz, m), jnp.float32),
        interpret=interpret_default(interpret),
        name="cell_list_force_planar",
    )(cpos, crad, cval, cpos, crad, cval)


def _window_force_kernel(
    qpos_ref,      # (4, T)  query tile: x, y, z, radius planes
    qcid_ref,      # (1, T)  int32 linear cell id per query (≥ n_cells = dead)
    wpos_ref,      # (4, BW) window block (same arrays, shifted index map)
    wcid_ref,      # (1, BW)
    out_ref,       # (4, T)  accumulated force (4th plane unused, keeps tiling)
    *,
    t: int,
    bw: int,
    h: int,
    nbw: int,
    dims: tuple,
    k: float,
    gamma: float,
):
    nx, ny, nz = dims
    n_cells = nx * ny * nz
    i = pl.program_id(0)
    w = pl.program_id(1)
    # Unclipped window-block id this program covers; the BlockSpec map clips
    # it into range for memory safety, so out-of-range sweeps would alias an
    # edge block — ok_w masks the whole segment instead of double-counting.
    jv = (i * t) // bw + w - h
    ok_w = (jv >= 0) & (jv < nbw)

    @pl.when(w == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    qx, qy, qz, qr = qpos_ref[0], qpos_ref[1], qpos_ref[2], qpos_ref[3]
    wx, wy, wz, wr = wpos_ref[0], wpos_ref[1], wpos_ref[2], wpos_ref[3]
    qcid = qcid_ref[0]
    wcid = wcid_ref[0]

    # 27-box adjacency straight from integer-decoded cell coordinates — the
    # Morton layout's job is to make the true neighbors *land in this window*;
    # the mask is what keeps the result exact.
    nzc = ny * nz
    qcx, qcy, qcz = qcid // nzc, (qcid // nz) % ny, qcid % nz
    wcx, wcy, wcz = wcid // nzc, (wcid // nz) % ny, wcid % nz

    # Self-pair exclusion by global row id (each pair appears in exactly one
    # (i, w) program because jv covers each window block once).
    qg = i * t + jax.lax.broadcasted_iota(jnp.int32, (t, 1), 0)
    wg = jv * bw + jax.lax.broadcasted_iota(jnp.int32, (1, bw), 1)

    pair = (
        (jnp.abs(qcx[:, None] - wcx[None, :]) <= 1)
        & (jnp.abs(qcy[:, None] - wcy[None, :]) <= 1)
        & (jnp.abs(qcz[:, None] - wcz[None, :]) <= 1)
        & (qg != wg)
        & ok_w
        # Compare after the broadcast: Mosaic has no boolean shape casts.
        & (qcid[:, None] < n_cells)
        & (wcid[None, :] < n_cells)
    )

    dx = qx[:, None] - wx[None, :]             # (T, BW)
    dy = qy[:, None] - wy[None, :]
    dz = qz[:, None] - wz[None, :]
    dist = jnp.sqrt(dx * dx + dy * dy + dz * dz + 1e-20)
    delta = qr[:, None] + wr[None, :] - dist
    overlap = (delta > 0.0) & pair
    rbar = qr[:, None] * wr[None, :] / jnp.maximum(
        qr[:, None] + wr[None, :], 1e-20
    )
    mag = k * delta - gamma * jnp.sqrt(jnp.maximum(rbar * delta, 0.0))
    scale = jnp.where(overlap, mag / dist, 0.0)

    out_ref[...] += jnp.stack(
        [
            jnp.sum(scale * dx, axis=1),
            jnp.sum(scale * dy, axis=1),
            jnp.sum(scale * dz, axis=1),
            jnp.zeros((t,), jnp.float32),
        ]
    )


@functools.partial(
    jax.jit,
    static_argnames=("dims", "k", "gamma", "block", "half_window", "interpret"),
)
def cell_window_force_planar(
    ppos: Array,    # (4, C) f32 agent-order planes: x, y, z, radius
    pcid: Array,    # (1, C) int32 linear cell id per agent (≥ n_cells = dead)
    dims: tuple,    # (nx, ny, nz) static grid dims
    k: float = 2.0,
    gamma: float = 1.0,
    block: int = 128,
    half_window: int = 8,
    interpret: bool | None = None,
) -> Array:
    """Morton-window contact forces over a layout-sorted pool, (4, C).

    The ``tile_order="morton"`` kernel (§5.4.2 payoff): agents are assumed
    sorted along the space-filling curve, so a contiguous block of ``block``
    agents covers a compact spatial region and all 27-box neighbors of a
    query tile live within ``± half_window`` *contiguous* blocks of it.  The
    grid is ``(C/T, 2·half_window + 1)``: program (i, w) folds window block
    ``i + w − half_window`` into query tile ``i`` — every load is a
    contiguous DMA of consecutive agents (near-zero gather cost), vs the
    cell-major path's O(n_cells·M) slot gather.

    Exactness is by masking, not by layout: pairs outside the 27-box
    adjacency (decoded from cell ids) contribute nothing, so the kernel is
    exact whenever the window *covers* each agent's neighborhood — the
    dispatcher (`repro.core.forces`) verifies that cheaply per step from
    cell counts and falls back otherwise.  With ``half_window ≥ C/block``
    the sweep is all-pairs and the result is exact for ANY layout (the
    parity tests exploit this).
    """
    t = bw = block
    c = ppos.shape[1]
    assert c % bw == 0, (c, bw)
    nbw = c // bw
    nw = 2 * half_window + 1

    def qry_idx(i, w):
        return (0, i)

    def win_idx(i, w):
        return (0, jnp.clip((i * t) // bw + w - half_window, 0, nbw - 1))

    kernel = functools.partial(
        _window_force_kernel,
        t=t, bw=bw, h=half_window, nbw=nbw, dims=dims, k=k, gamma=gamma,
    )
    return pl.pallas_call(
        kernel,
        grid=(c // t, nw),
        in_specs=[
            pl.BlockSpec((4, t), qry_idx),
            pl.BlockSpec((1, t), qry_idx),
            pl.BlockSpec((4, bw), win_idx),
            pl.BlockSpec((1, bw), win_idx),
        ],
        out_specs=pl.BlockSpec((4, t), qry_idx),
        out_shape=jax.ShapeDtypeStruct((4, c), jnp.float32),
        interpret=interpret_default(interpret),
        name="cell_window_force_planar",
    )(ppos, pcid, ppos, pcid)
