"""The one generator of initial populations.

A traffic file (``bench/traffic/<traffic>.json``) lists ``components``, each
a ``shape`` and the ``share`` of the agents it places, plus the shape's own
parameters.  A shape is a file of its own, ``bench/shapes/<shape>.py`` with
``draw(key, n, lo, hi, params) -> (n, 3) float32``, found by name.  What is
particular to a model (the kinds of its agents) comes from the
configuration's module: ``kinds(cfg, key, n) -> (n,) int32``.  Every agent
is drawn on the device in one jitted call from the seed, and every seed
places the same number of agents in each component.
"""

from __future__ import annotations

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp

SHAPES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "shapes")


def seed_key(seed: int):
    """A key for any whole seed: the low 32 bits seed it, the rest fold in."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def shape(name: str):
    path = os.path.join(SHAPES, f"{name}.py")
    if not os.path.exists(path):
        raise ValueError(f"no shape {name!r}: expected {path}")
    mod_name = f"shape_{name}"
    if mod_name not in sys.modules:
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[mod_name] = mod
    return sys.modules[mod_name]


def counts(n: int, components: list) -> list:
    """Agents per component: each its share, rounded; the last takes the
    rest."""
    total = sum(c["share"] for c in components)
    if abs(total - 1.0) > 1e-9:
        raise ValueError(f"component shares sum to {total}, not 1")
    out = [round(c["share"] * n) for c in components[:-1]]
    return out + [n - sum(out)]


def resized(cfg: dict, n: int) -> dict:
    """The configuration at ``n`` agents and the same density (CPU
    rehearsals): the space and the substance grids shrink with it."""
    out = dict(cfg, agents=n)
    edge = 2 * cfg["margin"] + (n / cfg["density_per_unit3"]) ** (1 / 3)
    lo = cfg["space"][0]
    out["space"] = [lo, lo + edge]
    out["substances"] = [
        dict(s, resolution=max(round(edge / s["voxel"]), 3))
        for s in cfg.get("substances", [])
    ]
    return out


def agents(cfg: dict, traffic: dict, seed: int, kinds) -> dict:
    """Initial positions, kinds and tags of ``cfg["agents"]`` agents."""
    n = cfg["agents"]
    lo, hi = cfg["space"]
    lo, hi = lo + cfg["margin"], hi - cfg["margin"]
    comps = traffic["components"]
    parts = [(shape(c["shape"]).draw, k, c) for c, k in
             zip(comps, counts(n, comps))]

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(parts) + 2)
        position = jnp.concatenate(
            [fn(kk, k, lo, hi, c) for (fn, k, c), kk in zip(parts, keys)])
        position = jax.random.permutation(keys[-2], position, axis=0)
        return {"position": position.astype(jnp.float32),
                "kind": kinds(cfg, keys[-1], n).astype(jnp.int32),
                "tag": jnp.arange(n, dtype=jnp.int32)}

    return draw(seed_key(seed))
