"""The proxy-host daemon: serves device-proxy sessions over TCP.

One daemon process per (simulated) machine. It listens on a port and, for
every accepted connection, runs a full :class:`~repro.proxy.service.
ProxyService` session on a thread — the same service class a locally
spawned proxy runs, now reachable from any host. Applications connect via
``DeviceProxy(endpoint=(addr, port))``; which application lands on which
daemon is the placement layer's decision (``repro.remote.placement``).

Killing the daemon (SIGKILL — the cross-host failure drill) severs every
session it hosts at once: each affected worker sees ProxyDiedError, asks
the coordinator for a survivor, and replays its API log there.

Standalone use (e.g. for ``launch/serve.py --proxy-endpoint``)::

    PYTHONPATH=src python -m repro.remote.host --port 7070
"""
from __future__ import annotations

import argparse
import multiprocessing as mp
import socket
import sys
import threading
from dataclasses import dataclass


@dataclass
class ProxyHostConfig:
    bind: str = "127.0.0.1"
    port: int = 0                       # 0: OS-assigned (reported via queue)
    sock_timeout_s: float = 1.0


def serve_forever(cfg: ProxyHostConfig, port_q=None, on_bound=None) -> None:
    """Bind, report the chosen port, serve sessions until killed.

    ``on_bound(port)`` runs after the listener exists — registration with
    a coordinator belongs there, never before the bind (an endpoint must
    not be advertised while nothing is accepting on it).
    """
    from repro.coord.protocol import Connection
    from repro.obs import trace as obs_trace
    from repro.proxy.service import ProxyService
    from repro.runtime.env import enable_compile_cache

    enable_compile_cache()

    listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    listener.bind((cfg.bind, cfg.port))
    listener.listen(64)
    port = listener.getsockname()[1]
    obs_trace.enable_from_env(f"proxyhost-{port}")
    if port_q is not None:
        port_q.put(port)
    else:
        print(f"[proxy-host] serving on {cfg.bind}:{port}", flush=True)
    if on_bound is not None:
        on_bound(port)

    def session(sock: socket.socket) -> None:
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        conn = Connection(sock)
        conn.settimeout(cfg.sock_timeout_s)
        obs_trace.instant("host.session_open", port=port)
        try:
            ProxyService(conn).serve()
        finally:
            conn.close()
            obs_trace.instant("host.session_close", port=port)

    while True:
        try:
            sock, _ = listener.accept()
        except OSError:
            return
        threading.Thread(
            target=session, args=(sock,), name="proxy-session", daemon=True
        ).start()


def proxy_host_entry(cfg: ProxyHostConfig, port_q) -> int:
    """multiprocessing spawn target."""
    serve_forever(cfg, port_q)
    return 0


class ProxyHostHandle:
    """Launcher-side handle on one daemon process."""

    def __init__(
        self,
        name: str,
        *,
        bind: str = "127.0.0.1",
        mp_context: str = "spawn",
        start_timeout_s: float = 120.0,
    ):
        self.name = name
        self.cfg = ProxyHostConfig(bind=bind)
        self.ctx = mp.get_context(mp_context)
        self.start_timeout_s = start_timeout_s
        self.proc: mp.Process | None = None
        self.port: int | None = None

    def start(self) -> "ProxyHostHandle":
        q = self.ctx.Queue()
        self.proc = self.ctx.Process(
            target=proxy_host_entry, args=(self.cfg, q),
            name=f"crum-proxy-host-{self.name}", daemon=True,
        )
        self.proc.start()
        try:
            self.port = int(q.get(timeout=self.start_timeout_s))
        except Exception:
            self.terminate()
            raise RuntimeError(
                f"proxy host {self.name} did not report a port within "
                f"{self.start_timeout_s}s"
            ) from None
        return self

    @property
    def addr(self) -> tuple[str, int]:
        assert self.port is not None, "call start() first"
        return self.cfg.bind, self.port

    @property
    def pid(self) -> int | None:
        return self.proc.pid if self.proc is not None else None

    def alive(self) -> bool:
        return self.proc is not None and self.proc.is_alive()

    def kill(self) -> None:
        """SIGKILL — the proxy-host failure drill. Every session dies."""
        if self.proc is not None and self.proc.is_alive():
            self.proc.kill()
            self.proc.join(timeout=10)

    def terminate(self) -> None:
        if self.proc is not None:
            if self.proc.is_alive():
                self.proc.terminate()
            self.proc.join(timeout=10)
            if self.proc.is_alive():
                self.proc.kill()
                self.proc.join(timeout=10)
            self.proc = None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--bind", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="listen port (0 = OS-assigned, printed at startup)")
    ap.add_argument("--coord", default=None, metavar="HOST:PORT",
                    help="register this endpoint with a cluster coordinator")
    ap.add_argument("--name", default=None,
                    help="endpoint name for registration (default host:port)")
    args = ap.parse_args(argv)

    cfg = ProxyHostConfig(bind=args.bind, port=args.port)
    on_bound = None
    if args.coord:
        from repro.remote.placement import register_proxy_endpoint
        from repro.remote.transport import endpoint_arg

        coord_addr = endpoint_arg(args.coord)

        def on_bound(port: int) -> None:
            # register only once the listener is live: advertising an
            # endpoint nothing accepts on would hand workers a
            # connection-refused assignment
            name = args.name or f"{cfg.bind}:{port}"
            register_proxy_endpoint(
                coord_addr, name=name, addr=cfg.bind, port=port
            )
            print(f"[proxy-host] registered as {name!r}", flush=True)

    serve_forever(cfg, on_bound=on_bound)
    return 0


if __name__ == "__main__":
    sys.exit(main())
