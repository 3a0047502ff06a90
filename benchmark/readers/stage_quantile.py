"""Reader kind `stage_quantile`: a quantile of one stage's samples inside
the window, from the log-scale `buckets` the stage clock prints at
/v1/debug/stages (cumulative, so the window is the difference between
the two snapshots).

spec: {"stage": name, "q": 0.5, "scale": 1e3}
value = the q-quantile in seconds * scale, linear inside the bucket it
falls in; the open last bucket reads as its lower edge. Nothing
recorded in the window, or a program that prints no buckets -> None.
"""


def read(spec: dict, ctx: dict):
    edges = ctx["stages1"].get("bucket_edges_s")
    after = ctx["stages1"]["stages"].get(spec["stage"], {}).get("buckets")
    if not edges or not after:
        return None
    before = ctx["stages0"]["stages"].get(spec["stage"], {}).get("buckets")
    counts = [b - a for a, b in zip(before or [0] * len(after), after)]
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
