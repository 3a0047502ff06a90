"""Native (C++) components, loaded via ctypes with graceful fallback.

The reference has no native components (pure Go, CGO_ENABLED=0); the ones
here exist because Python — unlike Go — can't hash millions of keys per
second per core, and host-side hashing sits on the serving hot path.

`hashlib_native` binds libguberhash.so (guberhash.cc): slot and ring
hashing, the presorts, the fused batch prep and run merges, the doors'
wire parsers and encoders, the traffic observers' fold.

The rule: the library is WHOLE or ABSENT. Build it from this tree with
`make -C gubernator_tpu/native` (the repo Makefile, the benchmark and
tests/conftest.py all do). Importing `hashlib_native` binds every symbol
or raises ImportError — the file is missing, does not load, or lacks a
symbol because it was built from another guberhash.cc (the error names
it). Nobody probes for one symbol. The package asks in ONE place,
core/hashing.native_lib(), which logs the reason once and answers None;
then the numpy / Python twins serve (presort, prep, merge, blake2b slot
hashes, SpaceSaving + HyperLogLog) and the doors' native folds decline
to the object path. The JAX-free GEB client (client_geb.py) cannot
import `core` and loads the module itself, for hashing alone.
"""
