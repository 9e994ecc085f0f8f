"""Server main (≙ run_server<Impl,Serv>, server_util.hpp:139-176).

    python -m jubatus_tpu.server classifier -f config/classifier/arow.json -p 9199
    python -m jubatus_tpu.server classifier --config-test -f conf.json
    python -m jubatus_tpu.server classifier -z /shared/cluster -n c1   # distributed
"""

from __future__ import annotations

import signal
import sys

from jubatus_tpu.server.args import parse_server_args
from jubatus_tpu.server.base import EngineServer


def main(argv=None) -> int:
    from jubatus_tpu.utils.compile_cache import configure as configure_cache

    configure_cache()
    args = parse_server_args(argv)
    from jubatus_tpu.utils.logger import install_sighup_reload, setup

    setup(f"juba{args.engine}", args.eth, args.rpc_port,
          logdir=args.logdir, log_config=args.log_config)
    install_sighup_reload(args.log_config)
    if args.config_test:
        # dry-construct and exit (server_util.hpp:142-152) — a LOCAL
        # check: never joins the jax world (that would block on the rest
        # of the fleet booting)
        srv = None
        try:
            srv = EngineServer.from_args(args)
        except Exception as e:  # broad-ok
            print(f"config error: {e}", file=sys.stderr)
            return 1
        finally:
            if srv is not None and srv.coord is not None:
                srv.coord.close()
        print("config ok")
        return 0
    coord = None
    if args.jax_processes > 1:
        # must run BEFORE anything initializes the XLA backend. The
        # coordinator session stays open and is handed to the server:
        # process 0's published jax endpoint is an ephemeral owned by it.
        from jubatus_tpu.coord import create_coordinator
        from jubatus_tpu.parallel import multihost

        coord = (create_coordinator(args.coordinator)
                 if not args.is_standalone else None)
        multihost.initialize(
            coordinator_address=args.jax_coordinator or None,
            num_processes=args.jax_processes,
            process_id=args.jax_process_id,
            coord=coord,
        )
    server = EngineServer.from_args(args, coord=coord)
    signal.signal(signal.SIGTERM, lambda *_: server.stop())
    signal.signal(signal.SIGINT, lambda *_: server.stop())
    server.start()
    server.join()
    return 0


if __name__ == "__main__":
    sys.exit(main())
