"""Real-size compiles for the real chip, without the chip.

The TPU compiler is installed next to the CPU backend the tests run on,
and it compiles for a chip that is DESCRIBED, not attached
(`topologies.get_topology_desc("tpu", "v5e:2x2")`). Interpret-mode and
CPU tests cannot see what it refuses — a Mosaic kernel's tiling, a
program that does not fit HBM, a sharding the partitioner rejects — so
three programs of the served path are compiled here at the size
chip_smoke.py runs them: no chip time, every later PR guarded.

Cost (PR 21, this sandbox; XLA's TPU compiler uses one core): the flat
sketch-tier decide 153 s, the Mosaic sweep 1.6 s, the 4-device mesh
decide 81 s. A whole decide program is minutes of XLA time, so this
file stays at three of them; it runs in one xdist worker. PR 30 adds
the small programs that take the 8 GiB table (about 6 s together).

Rules this file keeps (on-chip-measurement guide, section 2): the
topology is described inside a module-scoped fixture that skips when it
cannot be — never at import, never in a skipif/parametrize argument,
not autouse, not in conftest.py — because only one process may load
libtpu and every xdist worker imports every test file. Compiles run in
this process (a child could not load libtpu again) with the persistent
compile cache off: a described-device executable is written to the
cache but can never be read back.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, SingleDeviceSharding

import gubernator_tpu.core  # noqa: F401  (enables x64)
from gubernator_tpu.core import engine as engine_mod
from gubernator_tpu.core.kernels import packed_inputs_width
from gubernator_tpu.core.sketches import derive_sketch_config
from gubernator_tpu.core.store import StoreConfig
from gubernator_tpu.parallel.policy import ShardingPolicy
from gubernator_tpu.parallel.sharded import (
    MeshEngine,
    PartitionedEngine,
    TpuEngine,
)

NOW = 1_700_000_000_000
MIB = 1 << 20
# `GUBER_STORE_TARGET_KEYS=10000000` derives 16 ways x 2^20 bucket rows
# = 512 MiB (chip_smoke.py), plus the default 16 MiB v2 sketch; on the
# four-chip mesh a quarter of it each
SHARD_STORE = StoreConfig(rows=16, slots=1 << 18)
LADDER = (64, 256, 1024)  # buckets_for_limit(1000), the daemon's default


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sketch(mib):
    return derive_sketch_config(mib=mib, rows=0, derivation="v2")


def _shapes(x, sharding):
    """The pytree `x` of numpy arrays and scalars, as shapes laid out
    by `sharding`."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            np.shape(a), a.dtype, sharding=sharding
        ),
        x,
    )


def _shapes_only_engine(state_sharding_for):
    """A PartitionedEngine whose state is SHAPES: there is no device to
    hold an array, so the one state-construction seam (`_fresh`) yields
    ShapeDtypeStructs laid out exactly as the arrays would be."""

    class ShapesOnly(PartitionedEngine):
        def _fresh(self, make):
            lead = () if self.flat else (self.n,)
            return jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(
                    lead + s.shape, s.dtype,
                    sharding=state_sharding_for(self),
                ),
                jax.eval_shape(make),
            )

    return ShapesOnly


def _table_programs(rows, S, b=64):
    """name -> lowered, for the small programs that take the whole
    exact table int32[rows, 128] and give it back (the GLOBAL /
    promoter install, the full-lane install, the epoch rebase), plus
    the host reads' row gather under "rows_flat"; `S(shape, dtype)`
    makes the argument shapes."""
    from gubernator_tpu.core import kernels as K
    from gubernator_tpu.core.store import Store
    from gubernator_tpu.parallel.sharded import _rows_flat

    store = Store(data=S((rows, 128), jnp.int32))
    i32, u64, flag = S((b,), jnp.int32), S((b,), jnp.uint64), S((b,), jnp.bool_)
    return {
        "install": K.upsert_globals_jit.lower(
            store, u64, i32, i32, i32, flag, flag),
        "install_full": K.upsert_windows_jit.lower(
            store, u64, i32, i32, i32, i32, i32, i32, flag),
        "rebase": K.rebase_jit.lower(store, S((), jnp.int32)),
        "rows_flat": _rows_flat.lower(store.data, i32),
    }


def _captured(engine, attr, call):
    """The positional arguments `engine.<attr>` receives when `call()`
    runs — the engine's own padding, presort and group structure for a
    batch, taken from a small CPU engine instead of being re-derived."""
    seen = []
    orig = getattr(engine, attr)

    def spy(*a):
        seen.append(a)
        return orig(*a)

    setattr(engine, attr, spy)
    try:
        call()
    finally:
        setattr(engine, attr, orig)
    return seen[-1]


def _batch(keys):
    n = keys.shape[0]
    ones = np.ones(n, np.int64)
    return dict(
        key_hash=keys, hits=ones, limit=ones * 10, duration=ones * 1000,
        algo=np.zeros(n, np.int32), gnp=np.zeros(n, bool), now=NOW,
    )


@pytest.mark.parametrize("slots,temp_limit", [
    (1 << 20, 512 * MIB),  # zipf10m
    # the table that fills a chip (exact100m, PR 30): a second table,
    # or a pass that is not in place, cannot hide under this limit. The
    # temporaries read 2.6 MB at both row counts with the scatter told
    # its indices are sorted (PR 30) and without (PR 31).
    (1 << 24, 3 * MIB),
])
def test_flat_sketch_decide_1024_rung_compiles_for_v5e(
    topo, no_compile_cache, slots, temp_limit
):
    """The program a one-chip daemon serves: decide_presorted_sketch at
    the 1024 rung, store + 16 MiB sketch, both donated — at 2^20 rows
    (512 MiB, zipf10m) and at 2^24 (8 GiB, exact100m), where the
    writeback's unhinted scatter-add (kernels.writeback_form) must
    still write into the donated table."""
    one = SingleDeviceSharding(topo.devices[0])
    small = TpuEngine(
        StoreConfig(rows=16, slots=1 << 10), buckets=LADDER,
        sketch=_sketch(1),
    )
    keys = np.arange(1, 1025, dtype=np.uint64) << np.uint64(32)
    _, _, packed_in, B, G = _captured(
        engine_mod, "_decide_packed_sketch_jit",
        lambda: small.decide_arrays(**_batch(keys)),
    )
    assert (B, G) == (1024, 1024)
    assert packed_in.shape == (packed_inputs_width(B, G),)
    real = _shapes_only_engine(lambda eng: one)(
        StoreConfig(rows=16, slots=slots), policy=ShardingPolicy.single(),
        buckets=LADDER, sketch=_sketch(16),
    )
    compiled = engine_mod._decide_packed_sketch_jit.lower(
        real.store, real.sketch, _shapes(packed_in, one), B, G
    ).compile()
    mem = compiled.memory_analysis()
    state = slots * 512 + 16 * MIB
    assert state <= mem.argument_size_in_bytes < state + MIB
    # store and sketch are donated: the outputs alias them, so the step
    # holds ONE copy of the state on a 16 GB chip
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < temp_limit
    # the batch comes in as ONE int32 parameter beside the state, and
    # its 64-bit hashes are rebuilt from two 32-bit segments by shifts
    # and ORs, which the TPU compiler keeps as the (low, high) pair it
    # holds a u64 in anyway: no 64-bit bitcast-convert for it to expand
    # into a loop over the batch
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    params = [ln for ln in entry.splitlines() if " parameter(" in ln]
    assert len(params) == 3, params
    assert sum("s32[%d]" % packed_in.shape[0] in ln for ln in params) == 1
    assert not re.search(r"[us]64\[[^\n]*bitcast-convert", text)
    assert " while(" not in text


def test_store_programs_hold_one_table_at_2_24_rows(topo, no_compile_cache):
    """`GUBER_STORE_TARGET_KEYS=100000000`: 16 ways x 2^24 rows = 8 GiB
    of a 16 GB chip (PR 30). Every program that takes the table beside
    the decide — the GLOBAL / promoter install and the full-lane
    install (the decide's own gather, plan and scatter-add writeback at
    rung 64), the epoch rebase, the host reads' row gather — compiles
    for the chip at the real row count in a second or so each, its
    output the donated table's buffer and its temporaries far under a
    table. Before PR 30 `rebase` did not compile here: 17.25 GB wanted
    of 15.75 (a reshape of the table is a copy of the table). The
    decide itself at this geometry compiles in ~160 s (the same
    program text as the 2^20-row one above, temporaries 2.6 MB: my
    compile, PR 30) and is left to the chip runs of
    `exact100m.geb-frames`."""
    one = SingleDeviceSharding(topo.devices[0])
    b = 64
    programs = _table_programs(
        1 << 24, lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, dtype, sharding=one), b)
    gather = programs.pop("rows_flat").compile().memory_analysis()
    assert gather.output_size_in_bytes == b * 128 * 4
    assert gather.temp_size_in_bytes < MIB
    for name, lowered in programs.items():
        mem = lowered.compile().memory_analysis()
        assert mem.alias_size_in_bytes == 8 << 30, (name, mem)
        assert mem.temp_size_in_bytes < 64 * MIB, (name, mem)


def test_pallas_sweep_compiles_to_a_mosaic_kernel(topo, no_compile_cache):
    """The one kernel CPU tests only ever interpret: the store sweep at
    2^15 bucket rows x B=32768, the regime `auto` selects it in."""
    from gubernator_tpu.core import pallas_sweep

    one = SingleDeviceSharding(topo.devices[0])
    B, buckets = 32768, 1 << 15
    compiled = jax.jit(pallas_sweep._apply_inline).lower(
        jax.ShapeDtypeStruct((buckets, 128), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((B, 128), jnp.int32, sharding=one),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mesh_sketch_decide_compiles_for_four_chips(topo, no_compile_cache):
    """GUBER_BACKEND=mesh GUBER_SHARDS=4: the shard_map decide over a
    Mesh of the four described devices, each holding a quarter of the
    store and its own sub-sketch, with no collective on the decide path."""
    small = MeshEngine(
        StoreConfig(rows=16, slots=1 << 10), devices=jax.devices()[:4],
        buckets=LADDER, sketch=_sketch(1),
    )
    keys = np.random.default_rng(1).integers(
        1, 2**63, 1000, np.int64
    ).astype(np.uint64)
    _, _, packed_in, B, G = _captured(
        small, "_step_sketch", lambda: small.decide_arrays(**_batch(keys))
    )
    # [n_shards, W]: each shard's sub-batch is one row, `now` included
    assert packed_in.shape == (4, packed_inputs_width(B, G))
    real = _shapes_only_engine(lambda eng: eng.store_sharding)(
        SHARD_STORE, policy=ShardingPolicy.over_mesh(topo.devices),
        buckets=LADDER, sketch=_sketch(16),
    )
    sharded = NamedSharding(real.mesh, real.policy.request_spec())
    compiled = real._step_sketch.lower(
        real.store, real.sketch, _shapes(packed_in, sharded), B, G
    ).compile()
    mem = compiled.memory_analysis()  # bytes on EACH device
    per_device = 128 * MIB + 16 * MIB
    assert per_device <= mem.argument_size_in_bytes < per_device + MIB
    assert mem.alias_size_in_bytes >= per_device
    text = compiled.as_text()
    for collective in ("all-reduce", "all-gather", "all-to-all",
                       "collective-permute"):
        assert collective not in text, collective
