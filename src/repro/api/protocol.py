"""``ParameterServerProtocol`` — the one server surface every engine,
endpoint and worker codes against.

``ShardedParameterServer`` implements it; the transport endpoint, the
PS workers and the process pool code against this surface, which
speaks the packed (rows, 512) wire format only —

    pull_packed / push_packed  packed (rows, 512) wire format
    pull_delta                 version-delta pull (changed shards only)
    pull_packed_shard /        per-shard packed regions (the unit the
    push_packed_shard          transport endpoints route on)
    snapshot / shutdown        lifecycle (``snapshot`` is the pytree read)
    add_worker / remove_worker elastic membership
    record_loss / metrics      accounting

Import-light on purpose: this module must be importable before jax and
without triggering the rest of ``repro.api`` machinery.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

Params = Any


@dataclasses.dataclass(frozen=True)
class DeltaPull:
    """Result of a version-delta pull: only the shards that advanced.

    ``versions`` is the server's per-shard version vector at snapshot
    time (the client stores it and sends it back on its next
    ``pull_delta``); ``shards``/``regions`` are the parallel lists of
    advanced shard ids and their packed ``(rows, 512)`` regions
    (jax arrays server-side, numpy host buffers on a transport
    client).  ``full`` marks a full-snapshot fallback — the client's
    version vector did not match the server's shard arity (or ran
    ahead of it), so every non-empty shard's region is included and
    the client should treat the patch as a complete rebuild.
    ``epoch`` is the server's live-reshard epoch at snapshot time: a
    change from the client's last-seen epoch means the shard arity
    (and wire layout) moved under it — the reply is already a full
    snapshot in the NEW layout, and the client must rebuild its
    plan/buffers before patching.
    """

    versions: Tuple[int, ...]
    shards: Tuple[int, ...] = ()
    regions: Tuple[Any, ...] = ()
    full: bool = False
    epoch: int = 0

    @property
    def empty(self) -> bool:
        return not self.shards


class ParameterServerProtocol:
    """Base class of the server surface: the calls a server must
    answer, and the lifecycle aliases every server shares."""

    stopped: bool = False
    version: int = 0
    n_shards: int = 1

    def shard_versions(self) -> List[int]:
        raise NotImplementedError

    # ----------------------------------------------------- packed wire
    def pull_packed(self, worker: int = -1):
        raise NotImplementedError

    def push_packed(self, worker: int, wire) -> None:
        raise NotImplementedError

    def pull_delta(self, worker: int,
                   versions: Optional[Sequence[int]]) -> DeltaPull:
        """Version-delta pull: the shards that advanced past the
        client's ``versions`` vector, or a full-snapshot fallback on a
        vector mismatch."""
        raise NotImplementedError

    # ------------------------------------------------------- per shard
    def pull_packed_shard(self, shard: int, worker: int = -1):
        raise NotImplementedError

    def push_packed_shard(self, worker: int, shard: int, buf) -> None:
        raise NotImplementedError

    # ------------------------------------------------------- lifecycle
    def stop(self) -> None:
        raise NotImplementedError

    def shutdown(self) -> None:
        """Release every gated worker and refuse new work.  Alias of
        ``stop`` today; sessions call this so servers can grow teardown
        steps without touching call sites."""
        self.stop()

    def snapshot(self) -> Params:
        """A pytree snapshot of the global weights."""
        raise NotImplementedError

    @property
    def params(self) -> Params:
        return self.snapshot()

    # ------------------------------------------------------ membership
    def add_worker(self, worker: int) -> None:
        raise NotImplementedError

    def remove_worker(self, worker: int) -> None:
        raise NotImplementedError

    # ------------------------------------------------------ accounting
    def record_loss(self, step: int, loss: float) -> None:
        raise NotImplementedError

    def staleness_profile(self) -> Dict:
        raise NotImplementedError
