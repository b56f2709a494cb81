"""Helpers the configurations' checks share: state transfer, the layout
sort, matching agents by tag and relative gaps."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import reference as ref


def on_device(s: dict) -> dict:
    s = jax.tree.map(jnp.asarray, s)
    return dict(s, step=int(s["step"]))


def to_host(s: dict) -> dict:
    out = jax.tree.map(lambda a: np.asarray(jax.device_get(a)), s)
    return dict(out, position=out["position"].astype(np.float32),
                grids={k: v.astype(np.float32)
                       for k, v in out.get("grids", {}).items()})


def sort_if_due(s: dict, cfg: dict, lo: float, box: float, n: int) -> dict:
    """Apply the layout sort on the iterations where it fires."""
    if s["step"] % cfg["sort_frequency"]:
        return s
    order = ref.layout_order(s["position"], s["alive"], lo, box, n)
    take = lambda a: jnp.take(a, order, axis=0)
    per_agent = ("position", "diameter", "kind", "age", "alive")
    out = dict(s, **{k: take(s[k]) for k in per_agent})
    out["attrs"] = {k: take(v) for k, v in s["attrs"].items()}
    return out


def by_tag(s: dict) -> dict:
    """Live agents' arrays reordered by tag (tags 0..n-1, each once)."""
    alive = s["alive"]
    tag = s["attrs"]["tag"][alive]
    order = np.argsort(tag, kind="stable")
    pick = lambda a: a[alive][order]
    return {"tag": tag[order], "position": pick(s["position"]),
            "kind": pick(s["kind"]),
            "attrs": {k: pick(v) for k, v in s["attrs"].items()}}


def layout_mismatch(got: dict, want: dict) -> int:
    """Slots whose agent differs from the reference's slot order, plus any
    agent lost, duplicated or left dead."""
    tg = np.where(got["alive"], got["attrs"]["tag"], -1)
    tw = np.where(want["alive"], want["attrs"]["tag"], -1)
    live = np.sort(tg[tg >= 0])
    lost = len(np.setdiff1d(tw[tw >= 0], live)) + len(live) - len(np.unique(live))
    return int((tg != tw).sum()) + lost


def rel_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Largest absolute difference over the reference's largest magnitude."""
    scale = float(np.abs(want).max())
    return float(np.abs(got - want).max()) / max(scale, 1e-30)
