"""Correctness of a distributed soma-clustering chunk: the one-chip
reference over the whole space, agents matched by tag in global
coordinates.  The slot order is per chip, so no layout is compared."""

from __future__ import annotations

from checks import soma_clustering as one_chip

LIMITS = {k: v for k, v in one_chip.LIMITS.items() if k != "layout_mismatch"}
reference = one_chip.reference


def compare(cfg: dict, got: dict, want: dict) -> dict:
    out = one_chip.compare(cfg, got, want)
    del out["layout_mismatch"]
    return out
