"""Process-boundary transports for the packed wire format.

The packed (rows, 512) buffer that PR 2 made the native push/pull
representation gets its bytes-on-the-wire story here: a ``Transport``
ABC with ``inproc`` / ``tcp`` / ``shmem`` backends, a
``PSServerEndpoint`` that serves push/pull/policy-gate RPCs for the
``ShardedParameterServer`` (with per-shard routing), and the worker-side ``PSTransportClient``.  Frame layout
lives in ``repro.wireformat``; see README.md in this directory for the
byte-level format.

Client-side imports stay jax-free so spawned worker processes can
frame bytes without paying the accelerator-runtime import.
"""

from __future__ import annotations

from typing import Tuple

from repro.transport.base import (
    Channel,
    PSTransportClient,
    Transport,
    TransportClosed,
)
from repro.transport.endpoint import PSServerEndpoint, ShardRouter
from repro.transport.inproc import InprocTransport
from repro.transport.shmem import ShmemChannel, ShmemTransport
from repro.transport.tcp import TcpChannel, TcpTransport

#: CLI surface (``train.py --transport``) and benchmark axis.
BACKENDS = ("inproc", "tcp", "shmem")


def make_transport(kind: str, *, n_workers: int = 0, host: str = "127.0.0.1",
                   port: int = 0) -> Transport:
    """Construct (but do not start) one transport backend."""
    if kind == "inproc":
        return InprocTransport()
    if kind == "tcp":
        return TcpTransport(host=host, port=port)
    if kind == "shmem":
        if n_workers < 1:
            raise ValueError("shmem needs n_workers (one slot per worker)")
        return ShmemTransport(n_workers)
    raise ValueError(f"unknown transport {kind!r} (have {BACKENDS})")


def connect(address: Tuple, worker_id: int, *,
            compress: str = "none") -> PSTransportClient:
    """Reconstruct a client from a picklable transport address — the
    entry point for spawned worker processes."""
    from repro.transport import inproc, shmem, tcp

    dispatch = {"inproc": inproc.connect, "tcp": tcp.connect,
                "shmem": shmem.connect}
    if not address or address[0] not in dispatch:
        raise ValueError(f"unknown transport address {address!r}")
    return dispatch[address[0]](address, worker_id, compress=compress)


__all__ = [
    "BACKENDS",
    "Channel",
    "InprocTransport",
    "PSServerEndpoint",
    "PSTransportClient",
    "ShardRouter",
    "ShmemChannel",
    "ShmemTransport",
    "TcpChannel",
    "TcpTransport",
    "Transport",
    "TransportClosed",
    "connect",
    "make_transport",
]
