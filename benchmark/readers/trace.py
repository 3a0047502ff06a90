"""Reader kind `trace`: a number from the reduced device trace
(trace_reduce.py). spec: {"field": "step_us" | "idle_share_pct"}.
A stand-in trace (no device plane: the CPU rehearsal) reads as nothing.
"""


def step_seconds(ctx: dict):
    step = ctx["trace"]["step"]
    if ctx["trace"]["stand_in"] or not step or not step["executions"]:
        return None
    return step["seconds"] / step["executions"]


def read(spec: dict, ctx: dict):
    tr = ctx["trace"]
    if tr["stand_in"]:
        return None
    if spec["field"] == "step_us":
        s = step_seconds(ctx)
        return None if s is None else s * 1e6
    if spec["field"] == "idle_share_pct":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    raise ValueError(f"trace reader knows no field '{spec['field']}'")
