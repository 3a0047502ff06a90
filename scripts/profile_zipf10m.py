"""Why is BASELINE config 4 (zipf 10M keys, 1 GiB store) ~8x below the
100k-key flagship? This sweep separates the two candidate causes:

- **working-set size** (HBM): the slot store's gathers/scatters range
  over `slots * rows * 32B`; a 1 GiB table defeats any on-chip
  locality while a 16 MiB one doesn't.
- **unique-group count** (kernel work): store I/O runs at unique-key
  granularity (core/kernels.py group structure). A 16k zipf batch over
  100k keys repeats its heavy hitters (few unique groups, small group
  rung); over 10M keys nearly every row is unique (G ~= B, the widest
  rung plus maximal gather/scatter traffic).

Grid: key_space x store_slots at fixed B=16384 zipf(1.2) batches, each
cell reporting decisions/s plus the mean unique-key count per batch.
Reading the result: if throughput tracks store size at fixed keys, the
floor is memory; if it tracks key count at fixed store size, it's the
group structure. (r5 finding: it is overwhelmingly the unique-group
count — see BENCH_ZIPF10M_PROFILE_r5.json.)

Run on the real chip: python scripts/profile_zipf10m.py
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from scripts.bench_scenarios import (  # noqa: E402
    R,
    _measure_kernel,
    _zipf_key_hashes,
    log,
)


def unique_stats(key_space, B=16384):
    zipf, _ = _zipf_key_hashes(key_space, B)
    uniq = [len(np.unique(zipf[r])) for r in range(R)]
    return round(float(np.mean(uniq)), 1)


def main():
    from gubernator_tpu.core.store import StoreConfig

    import gubernator_tpu.core  # noqa: F401

    rows = []
    grid_keys = (100_000, 1_000_000, 10_000_000)
    # 2^20 (512 MiB) is the measured LEVER for config 4: 10M keys fit
    # it at load 0.60 and run ~1.75x faster than the 1 GiB table —
    # right-size the store to ~2-3x live keys instead of provisioning
    # footprint you pay for on every random access
    grid_slots = (1 << 15, 1 << 18, 1 << 20, 1 << 21)
    for keys in grid_keys:
        uniq = unique_stats(keys)
        for slots in grid_slots:
            capacity = slots * 16
            load = keys / capacity
            if load > 1.0:
                # an overloaded store measures eviction churn, not the
                # question at hand
                continue
            v = _measure_kernel(
                StoreConfig(rows=16, slots=slots), keys, "mixed"
            )
            row = dict(
                key_space=keys,
                store_slots=slots,
                store_mib=round(capacity * 32 / (1 << 20)),
                load_factor=round(load, 3),
                mean_unique_per_16k_batch=uniq,
                decisions_per_sec=round(v, 1),
            )
            rows.append(row)
            log(row)

    # The mechanism behind the footprint cost (r5): XLA lowers the
    # writeback scatter as a FULL-TABLE pass (profiled: the scatter
    # fusion's device time is 51us at 16 MiB and 3324us at 1 GiB —
    # read+write of the whole table at ~650 GB/s), paid once per
    # BATCH. Batch depth therefore amortizes it: the second lever.
    batch_rows = []
    from scripts.bench_scenarios import _scenario_steps

    for B in (16384, 32768, 131072):
        v = _measure_kernel(
            StoreConfig(rows=16, slots=1 << 21), 10_000_000, "mixed",
            B=B, S=max(1, _scenario_steps() // max(1, B // 16384)),
        )
        row = dict(
            key_space=10_000_000, store_mib=1024, batch=B,
            decisions_per_sec=round(v, 1),
        )
        batch_rows.append(row)
        log(row)
    print(json.dumps({
        "schema": "zipf10m_profile_r5",
        "rows": rows,
        "scatter_full_pass_us": {"16MiB": 51.5, "1GiB": 3324.2},
        "batch_depth_rows": batch_rows,
    }))


if __name__ == "__main__":
    main()
