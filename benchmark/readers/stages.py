"""Reader kind `stages`: a mean from two /v1/debug/stages snapshots, one
at each end of the window (the stage clock keeps totals and counts only).

spec: {"sum_total_s": [stage, ...], "per_count_of": stage, "scale": 1e6}
value = sum of the stages' seconds in the window / that stage's count
in the window * scale. Nothing recorded in the window -> None. With
"node": "all" the ring's nodes' records are pooled (readers/nodes.py).
"""

from readers import nodes


def _delta(chosen, stage: str, field: str) -> float:
    return sum(
        n["stages1"]["stages"].get(stage, {}).get(field, 0)
        - n["stages0"]["stages"].get(stage, {}).get(field, 0)
        for n in chosen
    )


def read(spec: dict, ctx: dict):
    chosen = nodes.chosen(spec, ctx)
    count = _delta(chosen, spec["per_count_of"], "count")
    if count <= 0:
        return None
    total = sum(_delta(chosen, s, "total_s") for s in spec["sum_total_s"])
    return total / count * spec["scale"]
