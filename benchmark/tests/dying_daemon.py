#!/usr/bin/env python3
"""The daemon as a ring member that is lost under load, for the test
that shows a run with a dead node gives no result. Run in ONE node's
place through run.py's
`--daemon-argv '{"2": ["benchmark/tests/dying_daemon.py"]}'`.

The process exits (code 7, no drain) on the first forwarded batch that
carries a canary key: the generators send those from the warm-up on, so
the node boots, is preloaded and checked like the others and dies
before the window ends.
"""

import os
import sys


def install() -> None:
    from gubernator_tpu.serve.server import PeersV1Servicer

    serve = PeersV1Servicer.GetPeerRateLimits

    async def GetPeerRateLimits(self, wire, context):
        if b"canary" in bytes(wire):
            os._exit(7)
        return await serve(self, wire, context)

    PeersV1Servicer.GetPeerRateLimits = GetPeerRateLimits


if __name__ == "__main__":
    install()
    sys.argv = ["gubernator_tpu.cli.daemon"]
    from gubernator_tpu.cli.daemon import main

    sys.exit(main())
