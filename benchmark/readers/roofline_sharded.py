"""Reader kind `roofline_sharded`: the `roofline` reader for a store that
is split over `store.shards` devices, each of which runs the decide
program on its own sub-batch.

The step time in the device trace is already one shard's (trace_reduce.py
counts one execution a device plane and divides the summed seconds by
their number), and the peak is one chip's. So the bytes have to be one
shard's too: a shard's items are the window's mean device batch over the
shard count, and `store` in the configuration file is ONE shard's
geometry. The flat reader would give one chip's step the whole batch's
bytes and flatter the share by the shard count.

spec: {"bytes_fn": function in kernel_bytes.py}.
"""

import json
import os

import kernel_bytes
from readers import trace


def shard_share_pct(bytes_fn, items_a_batch: float, sketched_a_batch: float,
                    store: dict, step_seconds: float, hbm_bytes_per_s: float):
    """One shard's share of its chip's roofline, in %."""
    shards = store["shards"]
    need = bytes_fn(items_a_batch / shards, store,
                    sketched_items=sketched_a_batch / shards)
    return kernel_bytes.roofline_share_pct(need, step_seconds, hbm_bytes_per_s)


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
    return shard_share_pct(
        getattr(kernel_bytes, spec["bytes_fn"]),
        delta("device_batch_size_sum") / batches,
        delta("store_dropped_creates_total") / batches,
        ctx["config"]["store"], step_s,
        peaks[ctx["device_kind"]]["hbm_bytes_per_s"],
    )
