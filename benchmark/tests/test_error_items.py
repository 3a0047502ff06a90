"""An error item is not an admitted hit (PR 39). A door that answers
every n-th item with an error (what a ring's door node does when a
forward passes its deadline) leaves the status field 0; the tally must
count such an item as in doubt and as one malformed reply, never as
offered and admitted. The generators run here in-process against a fake
door, no daemon."""

import asyncio
import json
import os
import time
import types

import numpy as np
import pytest

from generators import closed_loop_frames, closed_loop_frames_global
from harness import workers

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EVERY = 7


def spec_of(mix: str, seconds=0.4):
    with open(os.path.join(BENCH, "traffic", mix + ".json")) as f:
        traffic = json.load(f)
    traffic.update(workers=1, inflight=2, items_per_frame=50, warmup_s=0.1,
                   prebuilt_frames_per_s=200, canary_every=4)
    return {"seed": 2**31 + 39, "seconds": seconds, "tag": "s39", "worker": 0,
            "traffic": traffic, "config": {"key_population": 5000},
            "geb": "nowhere:0"}


class ErringDoor:
    """Answers every item OVER_LIMIT, well-formed, but every EVERY-th
    item of all it is sent as an error: status 0, nothing echoed."""

    sent = errors = canary_errors = 0

    def __init__(self, addr, window, timeout):
        pass

    async def connect(self):
        return types.SimpleNamespace(window=2)

    async def get_rate_limits(self, reqs):
        await asyncio.sleep(0.001)
        cls, out = type(self), []
        for i, r in enumerate(reqs):
            cls.sent += 1
            if cls.sent % EVERY == 0:
                is_canary = r.unique_key.startswith("canary")
                cls.canary_errors += is_canary
                cls.errors += not is_canary
                out.append(types.SimpleNamespace(
                    status=0, limit=0, remaining=0, error="deadline exceeded"))
            else:
                out.append(types.SimpleNamespace(
                    status=1, limit=r.limit, remaining=0, error=""))
        return out

    def stats(self):
        return {}

    async def close(self):
        pass


class Pipe:
    def __init__(self):
        self.got = {}

    def send(self, msg):
        self.got[msg[0]] = msg[1]

    def recv(self):
        return ("go", time.monotonic() + 0.15)


@pytest.mark.parametrize("gen,mix", [
    (closed_loop_frames, "geb-frames"),
    (closed_loop_frames_global, "geb-frames-global")])
def test_a_door_that_errors_every_nth_item(monkeypatch, gen, mix):
    from gubernator_tpu import client_geb

    door = type("Door", (ErringDoor,), {})
    monkeypatch.setattr(client_geb, "AsyncGebClient", door)
    conn = Pipe()
    asyncio.run(gen._run(spec_of(mix), conn))
    done = conn.got["done"]
    t = done["tally"]
    frames = done["frames_sent"]
    canaries = sum(map(len, t["canary_replies"]))
    assert frames > 10 and door.errors > 50 and done["failed"] == 0
    # every item sent is either answered (offered) or an error (in doubt)
    assert t["offered"].sum() + t["in_doubt"].sum() + canaries == door.sent
    assert t["in_doubt"].sum() == door.errors
    assert t["malformed"] == door.errors  # PER ITEM: the run is not correct
    assert t["admitted"].sum() == 0  # the door admitted nothing: status 0
    #                                  beside an error is no admission


def test_a_frame_with_error_items_is_tallied_item_by_item():
    spec = spec_of("geb-frames")
    ids = np.array([[3, 5, 5, 8]])
    tally = workers.Tally(spec, ids)
    limit = tally.rules.of(ids[0])[0]
    tally.answered(ids[0], [0, 0, 1, 0], limit, [9, 9, 0, 9])
    tally.answered_with_errors(
        ids[0], [0, 0, 0, 1], [limit[0], 0, limit[2], limit[3]], [1, 0, 1, 0],
        [False, True, False, False])
    r = tally.result()
    assert r["ids"].tolist() == [3, 5, 8]
    assert r["offered"].tolist() == [2, 3, 2]
    assert r["admitted"].tolist() == [2, 2, 1]
    assert r["in_doubt"].tolist() == [0, 1, 0]
    assert r["malformed"] == 1
