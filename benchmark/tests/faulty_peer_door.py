#!/usr/bin/env python3
"""The daemon with the PEER door alone broken, for the test that shows a
wrong answer through `PeersV1/GetPeerRateLimits` cannot pass while V1
and GEB (the doors the harness's own checks use) answer right. Run in
the daemon's place through run.py's
`--daemon-argv '["benchmark/tests/faulty_peer_door.py"]'`.

Every second forwarded batch answers UNDER_LIMIT where the limiter said
OVER_LIMIT: the store is charged correctly, only the owner's reply to
its peer is wrong -- what a broken stitch or a stale shed verdict on
the owner side would look like from the forwarder.
"""

import itertools
import sys


def install() -> None:
    from gubernator_tpu.api.types import Status
    from gubernator_tpu.serve.instance import Instance

    n = itertools.count()
    serve = Instance.get_peer_rate_limits

    async def get_peer_rate_limits(self, reqs):
        resps = await serve(self, reqs)
        if next(n) % 2:
            for r in resps:
                r.status = Status.UNDER_LIMIT
        return resps

    Instance.get_peer_rate_limits = get_peer_rate_limits


if __name__ == "__main__":
    install()
    sys.argv = ["gubernator_tpu.cli.daemon"]
    from gubernator_tpu.cli.daemon import main

    sys.exit(main())
