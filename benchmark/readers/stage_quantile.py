"""Reader kind `stage_quantile`: a quantile of one stage's samples inside
the window, from the log-scale `buckets` the stage clock prints at
/v1/debug/stages (cumulative, so the window is the difference between
the two snapshots).

spec: {"stage": name, "q": 0.5, "scale": 1e3}
value = the q-quantile in seconds * scale, linear inside the bucket it
falls in; the open last bucket reads as its lower edge. Nothing
recorded in the window, or a program that prints no buckets -> None.
With "node": "all" the ring's nodes' samples are pooled (readers/nodes.py).
"""

from readers import nodes


def _window_counts(n: dict, stage: str):
    after = n["stages1"]["stages"].get(stage, {}).get("buckets")
    if not after:
        return None
    before = n["stages0"]["stages"].get(stage, {}).get("buckets")
    return [b - a for a, b in zip(before or [0] * len(after), after)]


def read(spec: dict, ctx: dict):
    chosen = nodes.chosen(spec, ctx)
    per_node = [c for c in (_window_counts(n, spec["stage"]) for n in chosen) if c]
    edges = ctx["stages1"].get("bucket_edges_s")
    if not edges or not per_node:  # a node that never saw the stage pools nothing
        return None
    counts = [sum(c) for c in zip(*per_node)]
    total = sum(counts)
    if total <= 0:
        return None
    rank, seen = spec["q"] * total, 0
    bounds = [0.0, *edges, edges[-1]]
    for i, n in enumerate(counts):
        if n and seen + n >= rank:
            lo, hi = bounds[i], bounds[i + 1]
            return (lo + (hi - lo) * (rank - seen) / n) * spec["scale"]
        seen += n
    return None
