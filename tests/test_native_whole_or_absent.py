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
SYMBOL = "guber_traffic_fold"


@pytest.fixture(scope="module")
def stale_dir(tmp_path_factory):
    """A directory that holds hashlib_native.py and a libguberhash.so
    built from a guberhash.cc that exports SYMBOL under another name."""
    tmp = tmp_path_factory.mktemp("stale_native")
    source = (NATIVE / "guberhash.cc").read_text()
    assert source.count(SYMBOL + "(") == 1
    (tmp / "guberhash.cc").write_text(
        source.replace(SYMBOL + "(", SYMBOL + "_renamed(")
    )
    for name in ("Makefile", "hashlib_native.py"):
        shutil.copy(NATIVE / name, tmp)
    try:
        subprocess.run(
            ["make", "-C", str(tmp)], check=True, capture_output=True
        )
    except (OSError, subprocess.CalledProcessError) as e:
        pytest.skip(f"no compiler to build the stale library with: {e}")
    return tmp


def test_a_library_that_lacks_one_symbol_does_not_import(stale_dir):
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
    stale_dir, monkeypatch, caplog
):
    """The package's one handle over the stale library: absent, the
    reason (the missing symbol) in ONE warning however often it is
    asked, and the Python hasher serving."""
    import gubernator_tpu.native as native_pkg

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
