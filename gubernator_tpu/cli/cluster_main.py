"""Standalone local test cluster.

`python -m gubernator_tpu.cli.cluster_main` boots a 6-node cluster on
127.0.0.1:9090-9095 and prints "Ready" (the reference's
cmd/gubernator-cluster, used by client e2e test fixtures).
"""

import sys
import time

from gubernator_tpu.cluster import LocalCluster


def main(argv=None) -> int:
    from gubernator_tpu.jaxenv import enable_compile_cache

    enable_compile_cache()
    addresses = [f"127.0.0.1:{p}" for p in range(9090, 9096)]
    cluster = LocalCluster(addresses, global_sync_wait=0.05)
    cluster.start()
    print("Ready", flush=True)
    try:
        while True:
            time.sleep(1)
    except KeyboardInterrupt:
        pass
    finally:
        cluster.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
