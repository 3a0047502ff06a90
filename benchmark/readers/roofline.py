"""Reader kind `roofline`: the least time the chip could take for one
step (bytes the algorithm needs / peak HBM bytes a second) over the
step's time in the device trace, in %. HBM bytes are the bound.

spec: {"bytes_fn": function in kernel_bytes.py}. Items a step are the
window's mean device batch (/metrics device_batch_size sum / count);
items the exact tier refused are the dropped creates. The peak comes
from peaks.json by device kind: an unknown kind is an error.
"""

import json
import os

import kernel_bytes
from readers import trace


def read(spec: dict, ctx: dict):
    step_s = trace.step_seconds(ctx)
    if step_s is None:
        return None

    def delta(name):
        return ctx["prom1"].get(name, 0.0) - ctx["prom0"].get(name, 0.0)

    batches = delta("device_batch_size_count")
    if batches <= 0:
        return None
    with open(os.path.join(os.path.dirname(kernel_bytes.__file__), "peaks.json")) as f:
        peaks = json.load(f)
    if ctx["device_kind"] not in peaks:
        raise KeyError(f"peaks.json has no device kind '{ctx['device_kind']}'")
    need = getattr(kernel_bytes, spec["bytes_fn"])(
        delta("device_batch_size_sum") / batches, ctx["config"]["store"],
        sketched_items=delta("store_dropped_creates_total") / batches,
    )
    return kernel_bytes.roofline_share_pct(
        need, step_s, peaks[ctx["device_kind"]]["hbm_bytes_per_s"]
    )
