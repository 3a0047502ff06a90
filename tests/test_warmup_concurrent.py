"""The warm-up compiles its ladder side by side (PR 26): every program
the served path can call is lowered and compiled concurrently by
`PartitionedEngine.compile_ahead`, then the warm-up traffic runs each
once and finds it compiled, then state and counters are reset.

Four engines — flat and 4-shard mesh, with and without the sketch tier,
on the daemon's default ladder (64/256/1024) — are warmed once each with
every XLA compilation recorded by name (JAX logs one line a program it
builds). What is held:

- the programs compiled are exactly the list the old one-at-a-time loop
  walked, written out below: one decide per (rung, group rung), one
  install per host rung, on the mesh one sync collective per host rung,
  and with the sketch tier the promoter's two host reads at five sizes;
- each was compiled ONCE: the traffic that follows `compile_ahead` inside
  the warm-up compiled none of them again;
- after the warm-up a decide at every (rung, group rung), an
  `update_globals` and a `sync_globals` at every host rung compile
  nothing at all;
- the store, the sketch and the stats are a freshly reset engine's.
"""

import collections
import logging
import re

import jax
import numpy as np
import pytest

from gubernator_tpu.core.engine import EngineStats, group_rungs
from gubernator_tpu.core.sketches import SketchConfig
from gubernator_tpu.core.store import StoreConfig
from gubernator_tpu.parallel import sharded
from gubernator_tpu.parallel.sharded import (
    MeshEngine,
    TpuEngine,
    sub_batch_ladder,
    warmup_batches,
)

LADDER = (64, 256, 1024)  # buckets_for_limit(1000), the daemon's default
STORE = StoreConfig(rows=16, slots=1 << 10)
SKETCH = SketchConfig(rows=2, width=1 << 12, counter_bytes=4)
NOW = 1_700_000_000_000

# what the old loops walked (parallel/sharded.py before PR 26): flat, for
# b in buckets for g in group_rungs(b) a decide, per bucket an
# update_globals, then the sketch reads at 64..1024; mesh, for r in
# sub_buckets for g in group_rungs(r) a decide, per host bucket an
# update_globals and a sync_globals, then the sketch reads
FLAT_PAIRS = [(64, 64), (256, 64), (256, 96), (256, 256), (1024, 240),
              (1024, 256), (1024, 384), (1024, 1024)]
SUB_LADDER = (64, 96, 128, 192, 256, 384, 512, 768, 1024)
MESH_PAIRS = 27  # 64, 96, 128, 192: one group rung; 256: 3; 384..1024: 4 each
READS = 5  # sketch_estimates and live_mask at 64, 128, 256, 512, 1024
EXPECTED = {
    "flat-sketch": {"_decide_packed_sketch_jit": 8, "upsert_globals_jit": 3,
                    "_sketch_min_flat": READS, "_rows_flat": READS},
    "flat-exact": {"_decide_packed_jit": 8, "upsert_globals_jit": 3},
    "mesh4-sketch": {"_local_decide_sketch": MESH_PAIRS,
                     "_shard_sync_globals": 3, "_shard_upsert": 3,
                     "_sketch_min_sharded": READS, "_rows_sharded": READS},
    "mesh4-exact": {"_local_decide": MESH_PAIRS, "_shard_sync_globals": 3,
                    "_shard_upsert": 3},
}


def test_the_ladders_written_out_are_the_engines():
    assert [(b, g) for b in LADDER for g in group_rungs(b)] == FLAT_PAIRS
    assert sub_batch_ladder(LADDER) == SUB_LADDER
    assert sum(len(group_rungs(r)) for r in SUB_LADDER) == MESH_PAIRS
    assert sharded.SKETCH_READ_RUNGS == (64, 128, 256, 512, 1024)


class Compiles(logging.Handler):
    """Names of the programs XLA builds while attached: JAX logs
    'Finished XLA compilation of jit(<name>) in <s> sec' for each."""

    LOGGER = logging.getLogger("jax._src.dispatch")

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.names = []

    def emit(self, record):
        m = re.match(r"Finished XLA compilation of jit\((.+?)\) in",
                     record.getMessage())
        if m:
            self.names.append(m.group(1))

    def __enter__(self):
        self._level = self.LOGGER.level
        self.LOGGER.setLevel(logging.DEBUG)
        self.LOGGER.addHandler(self)
        return self

    def __exit__(self, *exc):
        self.LOGGER.removeHandler(self)
        self.LOGGER.setLevel(self._level)


def make(case: str):
    kind, tier = case.split("-")
    sketch = SKETCH if tier == "sketch" else None
    if kind == "flat":
        return TpuEngine(STORE, buckets=LADDER, sketch=sketch)
    return MeshEngine(STORE, devices=jax.devices()[:4], buckets=LADDER,
                      sketch=sketch)


@pytest.fixture(scope="module", params=sorted(EXPECTED))
def warmed(request):
    """(case, engine after warm-up, programs compile_ahead built,
    programs the rest of the warm-up built)."""
    # the flat programs are module-level jits: what an earlier test of
    # this process compiled at these shapes must not hide a compile here
    jax.clear_caches()
    engine = make(request.param)
    compile_ahead, cut = engine.compile_ahead, []

    def spy():
        compile_ahead()
        cut.append(len(seen.names))

    engine.compile_ahead = spy
    with Compiles() as seen:
        engine.warmup(NOW)
    del engine.compile_ahead
    ahead = collections.Counter(seen.names[: cut[0]])
    after = collections.Counter(seen.names[cut[0]:])
    return request.param, engine, ahead, after


def test_compiles_the_programs_the_old_loop_walked(warmed):
    case, _, ahead, _ = warmed
    named = {k: v for k, v in ahead.items() if k in EXPECTED[case]}
    assert named == EXPECTED[case]
    # and nothing else of size: what is left are one-operation programs
    # of the eager index arithmetic (bucket_index, the owner hash)
    others = set(ahead) - set(EXPECTED[case])
    assert not {n for n in others if n.startswith(("_", "upsert"))}, others


def test_the_traffic_finds_every_program_compiled(warmed):
    case, _, _, after = warmed
    again = {k: v for k, v in after.items() if k in EXPECTED[case]}
    assert not again, again


def test_leaves_a_freshly_reset_engine(warmed):
    case, engine, _, _ = warmed
    fresh = make(case)
    got = jax.tree.leaves((engine.store, engine.sketch))
    want = jax.tree.leaves((fresh.store, fresh.sketch))
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.sharding == b.sharding
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert engine.stats.snapshot() == EngineStats().snapshot()
    assert engine.reset_generation >= 1


def test_later_calls_compile_nothing(warmed):
    _, engine, _, _ = warmed
    with Compiles() as seen:
        for batch in warmup_batches(engine):
            engine.decide_arrays(now=NOW, **batch)
        for b in LADDER:
            k = np.arange(1, b + 1, dtype=np.uint64)
            ones = np.ones(b, np.int64)
            engine.update_globals(
                key_hash=k, limit=ones, remaining=ones,
                reset_time=ones * NOW, is_over=np.zeros(b, bool), now=NOW,
            )
            engine.sync_globals(k, ones, ones * 1000, now=NOW)
    assert seen.names == []
