#!/usr/bin/env python3
"""The daemon with one fault put in, for the tests that show `correct`
can come out false. Run in the daemon's place through run.py's
`--daemon-argv '["benchmark/tests/faulty_daemon.py", "<fault>"]'`.

  lost_writes      THE CONTROL. Breaks "every acknowledged hit is read
                   back": every 16th device batch is decided with its
                   hits zeroed, so its callers are answered "admitted"
                   and nothing is charged -- the step a later change
                   would be tempted by (skip or defer the writeback).
  altered_answers  The timed path broken where an answer is produced:
                   every 5th device batch answers UNDER_LIMIT for all
                   its items.

Both seams are where every submit path of the engine ends
(PartitionedEngine._dispatch) and where every answer is fetched
(PartitionedEngine.decide_wait).
"""

import itertools
import sys


def install(fault: str) -> None:
    import numpy as np

    from gubernator_tpu.parallel.sharded import PartitionedEngine as Engine

    n = itertools.count()
    if fault == "lost_writes":
        dispatch = Engine._dispatch

        def _dispatch(self, req, groups, e_now):
            if next(n) % 16 == 15:
                req = req._replace(hits=np.zeros_like(np.asarray(req.hits)))
            return dispatch(self, req, groups, e_now)

        Engine._dispatch = _dispatch
    elif fault == "altered_answers":
        wait = Engine.decide_wait

        def decide_wait(self, handle):
            status, limit, remaining, reset = wait(self, handle)
            if next(n) % 5 == 4:
                status = np.zeros_like(status)
            return status, limit, remaining, reset

        Engine.decide_wait = decide_wait
    else:
        raise SystemExit(f"unknown fault '{fault}'")


if __name__ == "__main__":
    install(sys.argv[1])
    sys.argv = ["gubernator_tpu.cli.daemon"]
    from gubernator_tpu.cli.daemon import main

    sys.exit(main())
