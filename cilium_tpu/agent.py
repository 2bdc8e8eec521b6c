"""The agent process: Daemon + REST API on a unix socket.

The analog of the reference's `cilium-agent` binary (daemon/main.go):
constructs the Daemon (optionally against a remote kvstore and a
state dir for checkpoint/restore) and serves the api/v1 surface on a
unix socket for the CLI and other clients.

    python -m cilium_tpu.agent --socket /tmp/cilium-tpu.sock \
        [--kvstore host:port] [--state-dir DIR] [--node NAME]
"""

from __future__ import annotations

import argparse
import signal
import threading


def main() -> None:
    ap = argparse.ArgumentParser(prog="cilium-tpu-agent")
    ap.add_argument("--socket", required=True)
    ap.add_argument("--kvstore", default=None, help="host:port")
    ap.add_argument("--state-dir", default=None)
    ap.add_argument("--node", default="node-0")
    ap.add_argument(
        "--trace-sample-rate", type=float, default=None,
        help="span-plane head-sampling probability (default 1.0: "
        "trace every request; turn down under load)",
    )
    args = ap.parse_args()

    from cilium_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.trace_sample_rate is not None:
        from cilium_tpu import tracing

        tracing.tracer.sample_rate = args.trace_sample_rate

    kvstore = None
    if args.kvstore:
        from cilium_tpu.kvstore.client import RemoteBackend

        host, sep, port = args.kvstore.rpartition(":")
        if not sep or not port.isdigit():
            ap.error(
                f"--kvstore expects host:port, got {args.kvstore!r}"
            )
        kvstore = RemoteBackend(host=host or "127.0.0.1", port=int(port))

    from cilium_tpu.api.server import APIServer
    from cilium_tpu.daemon import Daemon

    daemon = Daemon(
        kvstore=kvstore,
        node_name=args.node,
        state_dir=args.state_dir,
    )
    server = APIServer(daemon, args.socket).start()
    stop = threading.Event()

    def _term(signum, frame):
        server.stop()
        stop.set()

    signal.signal(signal.SIGTERM, _term)
    print(f"cilium-tpu-agent serving on {args.socket}", flush=True)
    try:
        stop.wait()
    except KeyboardInterrupt:
        server.stop()


if __name__ == "__main__":
    main()
