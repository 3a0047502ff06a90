"""Reader kind `stages`: a mean from two /v1/debug/stages snapshots, one
at each end of the window (the stage clock keeps totals and counts only).

spec: {"sum_total_s": [stage, ...], "per_count_of": stage, "scale": 1e6}
value = sum of the stages' seconds in the window / that stage's count
in the window * scale. Nothing recorded in the window -> None.
"""


def _delta(ctx, stage: str, field: str) -> float:
    a = ctx["stages0"]["stages"].get(stage, {}).get(field, 0)
    b = ctx["stages1"]["stages"].get(stage, {}).get(field, 0)
    return b - a


def read(spec: dict, ctx: dict):
    count = _delta(ctx, spec["per_count_of"], "count")
    if count <= 0:
        return None
    total = sum(_delta(ctx, s, "total_s") for s in spec["sum_total_s"])
    return total / count * spec["scale"]
