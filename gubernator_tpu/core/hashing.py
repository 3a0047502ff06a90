"""Key hashing.

Two independent hash roles, mirroring the reference's split:

1. Ring hash — maps a key (or peer address) to a point on the consistent-hash
   ring used for peer ownership. The reference uses crc32.ChecksumIEEE
   (reference hash.go:40-42); we use the same function (zlib.crc32) so that
   ownership distribution characteristics match.

2. Slot hash — a 64-bit hash used to derive the d row-slot indices and the
   32-bit fingerprint tag of the device slot store. This hash is local to an
   instance (peers never need to agree on it), but must be stable across runs
   for debuggability. Batch hashing of many keys is on the serving hot path,
   so there is a C++ fast path (native/libguberhash) with a pure-Python
   fallback (blake2b).
"""

from __future__ import annotations

import hashlib
import logging
import zlib
from typing import Iterable, List

import numpy as np

log = logging.getLogger(__name__)


def ring_hash(key: str) -> int:
    """crc32 point on the ring, matching reference hash.go:40-42."""
    return zlib.crc32(key.encode("utf-8")) & 0xFFFFFFFF


def _slot_hash_py(key: str) -> int:
    """Pure-Python fallback 64-bit hash (blake2b-8)."""
    return int.from_bytes(
        hashlib.blake2b(key.encode("utf-8"), digest_size=8).digest(), "little"
    )


def _slot_hash_batch_py(keys: Iterable[str]) -> np.ndarray:
    return np.array([_slot_hash_py(k) for k in keys], dtype=np.uint64)


# The native library (gubernator_tpu/native) is loaded lazily, once.
# Native and fallback produce different hash values; that is fine — slot
# hashes are local to one process's store — but one process must use ONE
# implementation consistently, which the lazy singleton guarantees.
_native = None
_native_checked = False


def native_lib():
    """The loaded gubernator_tpu.native.hashlib_native module, or None:
    the ONE place this package asks whether libguberhash.so is there,
    and the one answer a process holds. The library is whole — built
    from this tree's guberhash.cc, every symbol bound — or absent: not
    built, unloadable, or built from another source (the import names
    the missing symbol). Absent, the numpy / Python twins serve and the
    doors' native folds decline to the object path; the reason is
    logged here, once."""
    global _native, _native_checked
    if not _native_checked:
        _native_checked = True
        try:
            from gubernator_tpu.native import hashlib_native

            _native = hashlib_native
        except Exception as e:
            _native = None
            log.warning(
                "libguberhash.so is absent, the numpy and Python forms "
                "serve: %s", e,
            )
    return _native


def slot_hash_batch(keys: List[str]) -> np.ndarray:
    """uint64[len(keys)] of slot hashes; uses the native extension if built."""
    lib = native_lib()
    if lib is not None:
        return lib.hash_batch(keys)
    return _slot_hash_batch_py(keys)


def using_native_hash() -> bool:
    """True when slot_hash_batch resolves to the native XXH64 hasher.

    A PRE-hashing peer (the compiled edge, the GEB client's fast
    framing) computes slot hashes in its own process; its keys land in
    the right store rows only when both sides run the SAME
    implementation. The bridge hello advertises this bit (HELLO_XXH64,
    serve/edge_bridge.py) so a fast client can verify agreement instead
    of silently splitting buckets between two hash functions."""
    return native_lib() is not None


def slot_hash(key: str) -> int:
    """64-bit slot hash of one key (same implementation as the batch path)."""
    return int(slot_hash_batch([key])[0])


def mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer — derives independent row hashes from one hash."""
    x = x.astype(np.uint64)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))
