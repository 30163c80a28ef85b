"""The served path as the program names it, for the per-layer readers
of the spans `plan-cache`, `launch`, `enqueue-fetch` and `resolve` and
of the collector's seconds and passes while the statement's root span
was open (`gc_s`, `gc_passes`). A flight of a
program without them has spans but not `gc_s`: every reader here then
finds nothing and returns None."""

from __future__ import annotations

import spans


def named(run) -> list:
    """The answered statements whose flight carries the new keys."""
    return [s for s in spans.spanned(run) if "gc_s" in s["flight"]]


def mean_ms(run, suffix: str):
    """`spans.mean_ms`, or None where no flight carries the new keys."""
    return spans.mean_ms(run, suffix) if named(run) else None


def unspanned_seconds(flight) -> float:
    """The root span's seconds less the union of its leaf spans (a leaf
    has no span nested in it): what no span names, the self time of
    every span that has children, at any depth."""
    rows = [(r["name"], r["start_s"], r["start_s"] + r["seconds"]) for r in flight["spans"]]
    roots = [r for r in rows if "/" not in r[0]]
    if not roots:
        return 0.0
    root = max(roots, key=lambda r: r[2] - r[1])

    def inside(child, parent):
        return (child is not parent and child[0].startswith(parent[0] + "/")
                and child[1] >= parent[1] and child[2] <= parent[2])

    leaves = sorted(
        (r[1], r[2]) for r in rows
        if inside(r, root) and not any(inside(o, r) for o in rows)
    )
    covered, edge = 0.0, root[1]
    for start, end in leaves:
        if end > edge:
            covered += end - max(start, edge)
            edge = end
    return (root[2] - root[1]) - covered
