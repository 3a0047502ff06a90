"""Generator processes: how the parent starts them, gives them one
common start instant, and collects what they saw; and the tallies every
generator kind keeps so the checks can hold the daemon to its limits.

A generator kind is a module `generators/<kind>.py` with

  DOOR                      the door its traffic uses ("grpc" | "geb")
  run_worker(spec, conn)    the child: build everything from the seed,
                            connect, send ("ready", info), wait for
                            ("go", t0), send warm-up traffic from
                            t0 - warmup_s, the timed traffic over
                            [t0, t0 + seconds), drain, send ("done", result)
  summarize(results, spec)  the parent: end-to-end metrics and counts

Instants are time.monotonic(), which all processes of one host share.
"""

from __future__ import annotations

import importlib
import multiprocessing
import time

import numpy as np

import check
from harness import keyspace

READY_TIMEOUT = 180.0


def load_kind(kind: str):
    return importlib.import_module(f"generators.{kind}")


def worker_main(kind: str, spec: dict, conn) -> None:
    import gc

    gc.disable()  # a collection in mid-window is lateness of our own making
    try:
        load_kind(kind).run_worker(spec, conn)
    except BaseException as e:  # reported to the parent, which fails the run
        conn.send(("error", f"{type(e).__name__}: {e}"))
        raise
    finally:
        conn.close()


class Fleet:
    """The generator processes of one run."""

    def __init__(self, kind: str, specs):
        ctx = multiprocessing.get_context("spawn")
        self.procs, self.conns = [], []
        for spec in specs:
            parent, child = ctx.Pipe()
            p = ctx.Process(target=worker_main, args=(kind, spec, child))
            p.start()
            child.close()
            self.procs.append(p)
            self.conns.append(parent)

    def _recv(self, tag: str, timeout: float):
        out = []
        deadline = time.monotonic() + timeout
        for i, c in enumerate(self.conns):
            if not c.poll(max(0.0, deadline - time.monotonic())):
                raise RuntimeError(f"generator {i}: no '{tag}' in {timeout:.0f} s")
            got, body = c.recv()
            if got != tag:
                raise RuntimeError(f"generator {i} sent '{got}': {body}")
            out.append(body)
        return out

    def wait_ready(self):
        return self._recv("ready", READY_TIMEOUT)

    def go(self, t0: float) -> None:
        for c in self.conns:
            c.send(("go", t0))

    def results(self, timeout: float):
        return self._recv("done", timeout)

    def close(self) -> None:
        """Every worker ended and waited for; one that lingers is killed."""
        for c in self.conns:
            c.close()
        for p in self.procs:
            p.join(10.0)
            if p.is_alive():
                p.kill()
                p.join()


def wait_until(t: float) -> None:
    while True:
        d = t - time.monotonic()
        if d <= 0:
            return
        time.sleep(min(d, 0.05))


class Tally:
    """Per key, over everything one worker sent (warm-up included, so a
    key's whole history is counted): hits offered in answered calls,
    hits admitted, hits whose answer was lost or an error; and replies
    that were not well-formed. Canary keys are kept apart, reply by reply."""

    def __init__(self, spec: dict, ids: np.ndarray):
        self.rules = keyspace.KeyRules(spec["traffic"])
        self.pool = np.unique(ids)
        self.pool_limit = self.rules.of(self.pool)[0]
        self.offered = np.zeros(len(self.pool), np.int64)
        self.admitted = np.zeros(len(self.pool), np.int64)
        self.in_doubt = np.zeros(len(self.pool), np.int64)
        self.malformed = 0
        self.canaries = check.canary_keys(
            spec["seed"], spec["worker"], spec["traffic"]["canaries_per_worker"],
            [keyspace.ALGORITHMS[a["algorithm"]]
             for a in spec["traffic"]["algorithms"]],
        )
        self.canary_replies = [[] for _ in self.canaries]

    def canary_req(self, j: int):
        key, limit, algo = self.canaries[j]
        return keyspace.req(key, 1, limit, check.DAY_MS, algo)

    def answered(self, ids, status, limit, remaining) -> None:
        """Arrays of one or many answered calls (canary items excluded)."""
        ids, status = np.asarray(ids).ravel(), np.asarray(status).ravel()
        idx = np.searchsorted(self.pool, ids)
        self.malformed += check.malformed(
            status, np.asarray(limit).ravel(), np.asarray(remaining).ravel(),
            self.pool_limit[idx],
        )
        np.add.at(self.offered, idx, 1)
        np.add.at(self.admitted, idx[status == 0], 1)

    def answered_with_errors(self, ids, status, limit, remaining, error) -> None:
        """One answered frame in which some items came back as errors
        (`error`: truth per item). An error item is no answer: its status
        field reads 0 and is not an admitted hit, and the hit may or may
        not have reached its owner. So each counts as in doubt and as one
        malformed reply (the run is not correct, and says by how many
        items); the rest of the frame is tallied as answered."""
        bad = np.asarray(error, bool).ravel()
        ids = np.asarray(ids).ravel()
        self.malformed += int(bad.sum())
        self.lost(ids[bad])
        self.answered(ids[~bad], *(np.asarray(x).ravel()[~bad]
                                   for x in (status, limit, remaining)))

    def lost(self, ids) -> None:
        np.add.at(self.in_doubt, np.searchsorted(self.pool, np.ravel(ids)), 1)

    def canary_answered(self, j: int, resp) -> None:
        self.canary_replies[j].append(
            (int(resp.status), int(resp.limit), int(resp.remaining))
        )

    def result(self) -> dict:
        touched = (self.offered + self.in_doubt) > 0
        return {
            "ids": self.pool[touched], "offered": self.offered[touched],
            "admitted": self.admitted[touched],
            "in_doubt": self.in_doubt[touched],
            "malformed": self.malformed,
            "canaries": self.canaries, "canary_replies": self.canary_replies,
        }


def merge_tallies(results):
    """Sum the workers' per-key tallies: (ids, offered, admitted, in_doubt)."""
    ids = np.concatenate([r["tally"]["ids"] for r in results])
    uniq, inv = np.unique(ids, return_inverse=True)
    out = []
    for field in ("offered", "admitted", "in_doubt"):
        tot = np.zeros(len(uniq), np.int64)
        np.add.at(tot, inv, np.concatenate([r["tally"][field] for r in results]))
        out.append(tot)
    return (uniq, *out)
