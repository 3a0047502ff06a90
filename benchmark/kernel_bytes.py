"""Bytes the algorithm needs for one decide step, from its shapes.

HBM bytes are the bound (a decide step does a few integer operations
per byte it moves). What the algorithm NEEDS is the bucket row of every
item read and written back, the sketch cells of the items the exact
tier refused, and the request and response arrays -- not the size of
the table. A step that passes over the whole table (ROADMAP S1: the
writeback's sorted scatter until PR 31; no step does now) shows as a
small share of this roofline: that is what the share is there to expose.

Distinct bucket rows touched <= items; the items are counted, so the
bytes are an upper bound of the need and the share is, if anything,
flattered.
"""

from __future__ import annotations

REQUEST_BYTES_PER_ITEM = 8 + 8 + 8 + 8 + 4  # key hash, hits, limit, duration, algo/flags
RESPONSE_BYTES_PER_ITEM = 4 + 8 + 8 + 8  # status, limit, remaining, reset


def decide_step_bytes(items: float, store: dict, sketched_items: float = 0.0,
                      sketch_rows: int = 2, sketch_cell_bytes: int = 4) -> float:
    row_bytes = store["ways"] * store["entry_bytes"]
    return (
        items * row_bytes * 2
        + sketched_items * sketch_rows * sketch_cell_bytes * 2
        + items * (REQUEST_BYTES_PER_ITEM + RESPONSE_BYTES_PER_ITEM)
    )


def roofline_share_pct(bytes_needed: float, step_seconds: float,
                       hbm_bytes_per_s: float) -> float:
    """Least time the chip could take over the time it took, in %."""
    return 100.0 * (bytes_needed / hbm_bytes_per_s) / step_seconds
