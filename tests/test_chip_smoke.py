"""CPU rehearsal of chip_smoke.py, and the boot rule it rests on.

chip_smoke.py is what the driver runs on the machine with the chip. Its
phases are rehearsed here at a tiny store with the CPU asked for BY
NAME — every phase must pass — and the script must still exit non-zero
without printing `ok: true`, because the device the daemon reported is
not a TPU. The second half pins the rule that makes that honest: a
device backend refuses to boot when JAX found no TPU and no platform
was named.
"""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent

PHASES = [
    "boot", "device", "store", "load", "checked", "peek", "metrics",
    "profile", "device_after_load", "drain", "boot", "done",
]


def test_rehearsal_passes_every_phase_and_still_refuses_a_cpu(tmp_path):
    # a copy holding what git would commit of the script's needs: the
    # smoke builds libguberhash.so from guberhash.cc itself, and must not
    # drop a .so into the checkout other tests are running from
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    shutil.copytree(
        ROOT / "gubernator_tpu", tmp_path / "gubernator_tpu",
        ignore=shutil.ignore_patterns(
            "*.so", "__pycache__", "guber-edge", "guber-edge-asan"
        ),
    )
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
        TMPDIR=str(tmp_path),
    )
    env.pop("XLA_FLAGS", None)  # one CPU device, as one chip
    env.pop("PYTHONPATH", None)
    r = subprocess.run(
        [sys.executable, "chip_smoke.py", "--rehearse",
         "--target-keys", "200000", "--load-keys", "20000"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=900,
    )
    lines = [json.loads(x) for x in r.stdout.splitlines() if x.startswith("{")]
    by_phase = {}
    for x in lines:
        by_phase.setdefault(x.get("phase"), []).append(x)
    assert [x.get("phase") for x in lines] == PHASES, r.stderr[-3000:]
    assert r.returncode != 0
    assert not any("ok" in x for x in lines)
    assert "not a chip run" in r.stderr

    cold, warm = by_phase["boot"]
    assert cold["cache_dir_was_empty"] and not warm["cache_dir_was_empty"]
    assert cold["cold"] and not warm["cold"]
    assert cold["cache_hits"] == 0 and warm["cache_hits"] > 0
    # the compile cache lands where the variable says, and only there
    assert os.listdir(tmp_path / "cache")
    assert not (tmp_path / ".jax_cache").exists()
    dev = by_phase["device"][0]
    assert dev["device"]["platform"] == "cpu"
    assert dev["device"]["count"] == 1
    assert dev["host_prep"] == dev["hasher"] == "native"
    assert by_phase["load"][0]["keys"] == 20000
    assert by_phase["load"][0]["wrong"] == 0
    checked = by_phase["checked"][0]
    assert checked["requests"] >= 300 and checked["mismatches"] == 0
    assert checked["over_limit_answers"] > 0
    assert by_phase["peek"][0]["wrong"] == 0
    assert by_phase["metrics"][0]["store_evictions_total"] == 0
    assert by_phase["profile"][0]["files"] > 0
    assert by_phase["drain"][0]["exit_code"] == 0


def test_smoke_fails_alone_in_a_directory(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    r = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def _conf(backend, **env):
    from gubernator_tpu.serve.config import config_from_env

    return config_from_env(
        {"GUBER_BACKEND": backend, "GUBER_STORE_SLOTS": "256", **env}
    )


@pytest.mark.parametrize("backend", ["tpu", "mesh"])
def test_device_backend_refuses_to_boot_without_a_tpu(monkeypatch, backend):
    """No TPU and no platform named: a boot error that says what to do,
    not a daemon serving from whatever JAX fell back to."""
    from gubernator_tpu.serve.server import make_backend

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError) as e:
        make_backend(_conf(backend))
    msg = str(e.value)
    assert f"GUBER_BACKEND={backend} needs a TPU" in msg
    assert "'cpu'" in msg and "JAX_PLATFORMS=cpu" in msg
    assert "GUBER_JAX_PLATFORM=cpu" in msg


def test_device_backend_boots_where_the_platform_is_named(monkeypatch):
    import jax

    from gubernator_tpu.serve.backends import ExactBackend, TpuBackend
    from gubernator_tpu.serve.server import make_backend

    # by JAX_PLATFORMS (what conftest and the driver's command set)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert isinstance(make_backend(_conf("tpu")), TpuBackend)
    # by GUBER_JAX_PLATFORM alone
    monkeypatch.delenv("JAX_PLATFORMS")
    assert isinstance(
        make_backend(_conf("tpu", GUBER_JAX_PLATFORM="cpu")), TpuBackend
    )
    # the exact backend touches no device and never asks
    assert isinstance(make_backend(_conf("exact")), ExactBackend)
    # and on a TPU nothing needs naming (steered here: no chip in tests)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert isinstance(make_backend(_conf("tpu")), TpuBackend)
