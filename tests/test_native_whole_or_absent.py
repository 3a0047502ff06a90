"""libguberhash.so is whole or absent (PR 45): a library that lacks ONE
symbol of this tree's guberhash.cc does not import, and to the package
(core/hashing.native_lib, the one place that asks) it is what a library
never built is — None, with the reason logged once.

The stale library here is real: a copy of guberhash.cc with one
exported name changed, compiled out of tree (nothing is dropped into
the checkout other tests run from) and loaded beside a copy of
hashlib_native.py under a private name.
"""

import importlib.util
import logging
import pathlib
import shutil
import subprocess
import sys

import pytest

from gubernator_tpu.core import hashing

NATIVE = (
    pathlib.Path(__file__).resolve().parent.parent / "gubernator_tpu" / "native"
)
#: one symbol the library has had since PR 40, and the shed screen's
#: two (PR 48): each is in the one block, so a build without it is absent
SYMBOLS = ["guber_traffic_fold", "guber_shed_screen", "guber_shed_observe"]


@pytest.fixture(scope="module", params=SYMBOLS)
def stale(request, tmp_path_factory):
    """(symbol, a directory that holds hashlib_native.py and a
    libguberhash.so built from a guberhash.cc that exports that symbol
    under another name)."""
    symbol = request.param
    tmp = tmp_path_factory.mktemp("stale_native")
    source = (NATIVE / "guberhash.cc").read_text()
    assert source.count(symbol + "(") == 1
    (tmp / "guberhash.cc").write_text(
        source.replace(symbol + "(", symbol + "_renamed(")
    )
    for name in ("Makefile", "hashlib_native.py"):
        shutil.copy(NATIVE / name, tmp)
    try:
        subprocess.run(
            ["make", "-C", str(tmp)], check=True, capture_output=True
        )
    except (OSError, subprocess.CalledProcessError) as e:
        pytest.skip(f"no compiler to build the stale library with: {e}")
    return symbol, tmp


def test_a_library_that_lacks_one_symbol_does_not_import(stale):
    SYMBOL, stale_dir = stale
    spec = importlib.util.spec_from_file_location(
        "_hashlib_native_stale", stale_dir / "hashlib_native.py"
    )
    mod = importlib.util.module_from_spec(spec)
    with pytest.raises(ImportError) as err:
        spec.loader.exec_module(mod)
    text = str(err.value)
    assert SYMBOL in text
    assert "make -C gubernator_tpu/native" in text
    # no half-bound module is left behind for a caller to probe
    assert not hasattr(mod, "traffic_fold")
    assert not hasattr(mod, "hash_batch")
    assert not hasattr(mod, "shed_screen")


def test_a_library_never_built_says_so_the_same_way(tmp_path):
    shutil.copy(NATIVE / "hashlib_native.py", tmp_path)
    spec = importlib.util.spec_from_file_location(
        "_hashlib_native_unbuilt", tmp_path / "hashlib_native.py"
    )
    with pytest.raises(ImportError) as err:
        spec.loader.exec_module(importlib.util.module_from_spec(spec))
    assert "not built" in str(err.value)
    assert "make -C gubernator_tpu/native" in str(err.value)


def test_native_lib_over_a_stale_library_is_none_and_says_why_once(
    stale, monkeypatch, caplog
):
    """The package's one handle over the stale library: absent, the
    reason (the missing symbol) in ONE warning however often it is
    asked, and the Python hasher serving."""
    import gubernator_tpu.native as native_pkg

    SYMBOL, stale_dir = stale
    # a process that has not asked yet, whose native package holds the
    # stale build
    monkeypatch.setattr(native_pkg, "__path__", [str(stale_dir)])
    monkeypatch.delattr(native_pkg, "hashlib_native", raising=False)
    monkeypatch.delitem(
        sys.modules, "gubernator_tpu.native.hashlib_native", raising=False
    )
    monkeypatch.setattr(hashing, "_native", None)
    monkeypatch.setattr(hashing, "_native_checked", False)
    with caplog.at_level(logging.WARNING, logger="gubernator_tpu"):
        assert hashing.native_lib() is None
        assert hashing.native_lib() is None
        assert not hashing.using_native_hash()
        got = hashing.slot_hash_batch(["a_b", "c_d"])
    assert got.tolist() == hashing._slot_hash_batch_py(["a_b", "c_d"]).tolist()
    said = [
        r.getMessage() for r in caplog.records
        if "libguberhash.so is absent" in r.getMessage()
    ]
    assert len(said) == 1
    assert SYMBOL in said[0] and "make -C gubernator_tpu/native" in said[0]
    assert "gubernator_tpu.native.hashlib_native" not in sys.modules


@pytest.mark.parametrize("present", [True, False])
def test_the_boot_log_names_the_shed_screens_body(
    monkeypatch, caplog, present
):
    """`shed screen:` beside the other native selections' lines, and
    the two counters on /metrics: the array consults a GEB frame makes
    are counted under the body that served them, and under no other."""
    import urllib.request

    from gubernator_tpu.api.types import RateLimitReq
    from gubernator_tpu.client_geb import GebClient
    from gubernator_tpu.cluster import LocalCluster
    from gubernator_tpu.core.store import StoreConfig
    from gubernator_tpu.serve import shedcache
    from gubernator_tpu.serve.backends import TpuBackend
    from tests._util import free_ports

    if present and shedcache._hn is None:
        pytest.skip("libguberhash.so is absent: "
                    "make -C gubernator_tpu/native")
    if not present:
        monkeypatch.setattr(shedcache, "_hn", None)

    def consults():
        with urllib.request.urlopen(
            f"http://127.0.0.1:{http}/metrics", timeout=10
        ) as r:
            text = r.read().decode()
        return {
            line.split()[0]: float(line.split()[1])
            for line in text.splitlines()
            if line.startswith(("shed_native_", "shed_numpy_", "shed_index_uses"))
        }

    grpc_port, http, geb = free_ports(3)
    addr = f"127.0.0.1:{grpc_port}"
    cluster = LocalCluster(
        [addr],
        backend_factory=lambda: TpuBackend(
            StoreConfig(rows=16, slots=1 << 8), buckets=(16, 64)
        ),
        http_addresses=[f"127.0.0.1:{http}"], geb_ports=[geb],
    )
    with caplog.at_level(logging.INFO, logger="gubernator_tpu"):
        cluster.start()
    try:
        before = consults()
        assert set(before) == {
            "shed_index_uses_total", "shed_native_consults_total",
            "shed_numpy_consults_total",
        }
        # five keys of limit 2, eight hits each a frame: over limit
        # from the first frame on, so the later frames find the cache
        # holding entries and consult its index on both sides
        reqs = [
            RateLimitReq(name="s", unique_key=f"k{i % 5}", hits=1,
                         limit=2, duration=60_000)
            for i in range(40)
        ]
        with GebClient(f"127.0.0.1:{geb}") as client:
            for _ in range(4):
                resps = client.get_rate_limits(reqs, timeout=30)
                assert not any(r.error for r in resps)
        after = consults()
    finally:
        cluster.stop()
    grown = {k: after[k] - before[k] for k in after}
    mine, other = (
        ("shed_native_consults_total", "shed_numpy_consults_total")
        if present else
        ("shed_numpy_consults_total", "shed_native_consults_total")
    )
    assert grown[mine] == grown["shed_index_uses_total"] > 0
    assert grown[other] == 0
    lines = [
        r.getMessage() for r in caplog.records
        if r.getMessage().startswith("shed screen:")
    ]
    assert len(lines) == 1
    assert ("one native call each" in lines[0]) is present
    assert ("numpy on the serving loop" in lines[0]) is not present
