"""Server daemon entry point.

`python -m gubernator_tpu.cli.daemon [--config FILE]` — configuration from
GUBER_* env vars with an optional KEY=value config file injected first
(the reference daemon's surface, cmd/gubernator/main.go + config.go).
"""

import argparse
import asyncio
import logging
import sys

from gubernator_tpu.serve.config import config_from_env, load_config_file
from gubernator_tpu.serve.logging_setup import setup_logging
from gubernator_tpu.serve.server import run_daemon


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description="gubernator-tpu daemon")
    parser.add_argument(
        "--config",
        default="",
        help="environment config file of KEY=value lines",
    )
    args = parser.parse_args(argv)

    env = None
    if args.config:
        env = load_config_file(args.config)
    conf = config_from_env(env)

    setup_logging(
        level="debug" if conf.debug else conf.log_level,
        json_format=conf.log_json,
    )

    # one compile cache for every entry point: JAX_COMPILATION_CACHE_DIR
    # when set, else <checkout>/.jax_cache (gubernator_tpu/jaxenv.py)
    from gubernator_tpu.jaxenv import enable_compile_cache, require_tpu

    log = logging.getLogger("gubernator_tpu.daemon")
    log.info("compile cache: %s", enable_compile_cache())

    if conf.dist_coordinator:
        # multi-host mesh: join the jax.distributed program first; then
        # process 0 serves while every other process runs the lockstep
        # follower loop until the leader closes the step pipe
        from gubernator_tpu.parallel.multihost import (
            MultiHostMeshEngine,
            initialize_distributed,
        )

        # fail fast on the misconfigurations that otherwise deadlock the
        # whole mesh inside a collective or an accept() loop
        if conf.dist_process_id == 0:
            if conf.backend != "multihost":
                raise SystemExit(
                    "GUBER_DIST_COORDINATOR is set but GUBER_BACKEND="
                    f"{conf.backend!r}; the leader must use "
                    "GUBER_BACKEND=multihost"
                )
            if len(conf.dist_followers) != conf.dist_num_processes - 1:
                raise SystemExit(
                    f"GUBER_DIST_FOLLOWERS lists "
                    f"{len(conf.dist_followers)} addresses but "
                    f"GUBER_DIST_NUM_PROCESSES={conf.dist_num_processes} "
                    "implies "
                    f"{conf.dist_num_processes - 1} followers"
                )
        elif not conf.dist_step_listen:
            raise SystemExit(
                "follower processes (GUBER_DIST_PROCESS_ID > 0) require "
                "GUBER_DIST_STEP_LISTEN"
            )

        if conf.jax_platform:
            import jax

            jax.config.update("jax_platforms", conf.jax_platform)
        initialize_distributed(
            conf.dist_coordinator,
            conf.dist_num_processes,
            conf.dist_process_id,
        )
        if conf.dist_process_id != 0:
            from gubernator_tpu.core.engine import buckets_for_limit

            # the leader checks in make_backend; a follower builds its
            # engine here and must refuse the same silent CPU fallback
            require_tpu("a multihost follower", conf.jax_platform)

            # the bucket ladder AND store geometry must match the
            # leader's exactly: warmup replays every bucket through the
            # step pipe and a follower missing one would die in
            # choose_bucket mid-lockstep; store_config() (not raw
            # rows/slots) so GUBER_STORE_MIB/TARGET_KEYS auto-sizing
            # derives the same shape on every process. Same for the
            # sketch geometry (r20): the hello handshake verifies both.
            eng = MultiHostMeshEngine(
                conf.store_config(),
                buckets=buckets_for_limit(conf.device_batch_limit),
                sketch=conf.sketch_config(),
            )
            eng.follower_loop(conf.dist_step_listen)
            return 0

    asyncio.run(run_daemon(conf))
    return 0


if __name__ == "__main__":
    sys.exit(main())
