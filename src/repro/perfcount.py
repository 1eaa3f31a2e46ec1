"""Hot-path event counters for the push/pull wire format.

The packed-wire acceptance contract ("one packed push performs zero
host-side per-leaf concatenations on the server, and at most one
``pallas_call`` per shard for apply plus one for compression") is
asserted by counting the events themselves, not by timing: wall time on
a CPU interpret-mode container says nothing about HBM traffic, but the
*number* of pack/unpack/concat/launch events per push is
backend-independent and exactly the quantity the packed format
eliminates.

Instrumented sites:

  * ``leaf_concats``  — every ``jnp.concatenate`` over per-leaf pieces
    (``ShardPlan.assemble``, ``pack_shard`` with >1 leaf),
  * ``packs`` / ``unpacks`` — pytree <-> packed-buffer boundary
    crossings (``pack_shard`` / ``unpack_shard`` and the plan-level
    ``pack`` / ``unpack``),
  * ``pallas_calls``  — kernel launches (``fused_update``, the fused
    compressors),
  * ``apply_launches_saved`` — contributions folded into an already-
    counted ``fused_update_batched`` launch by the coalescing window,
  * ``delta_bytes_tx`` / ``full_pull_bytes_avoided`` — version-delta
    pull accounting (``pull_delta`` on either server),
  * ``pulls`` / ``pull_assemble_bytes`` / ``h2d_bytes`` — the device
    copies a worker's pull costs: the pulls workers made, the bytes of
    the sharded server's shard concat (on a cache miss), and the bytes
    of the host buffers a delta or process-transport pull uploads.

Counters are plain ints bumped under the GIL — cheap enough to stay on
permanently, precise enough for the single-threaded benchmark and test
probes that read them (multi-threaded runs should treat the numbers as
approximate).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping


@dataclasses.dataclass
class _CounterBase:
    """Shared reset/snapshot/delta over a dataclass of int fields."""

    def reset(self) -> None:
        for f in dataclasses.fields(self):
            setattr(self, f.name, 0)

    def snapshot(self) -> Dict[str, int]:
        return {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)}

    def delta(self, before: Dict[str, int]) -> Dict[str, int]:
        return {k: v - before.get(k, 0) for k, v in self.snapshot().items()}

    def add(self, counts: Mapping[str, Any]) -> None:
        """Bump each field by ``counts[field]`` (int-like)."""
        for f in dataclasses.fields(self):
            setattr(self, f.name,
                    getattr(self, f.name) + int(counts[f.name]))


@dataclasses.dataclass
class HotPathCounters(_CounterBase):
    leaf_concats: int = 0
    packs: int = 0
    unpacks: int = 0
    pallas_calls: int = 0
    #: Launches the coalesced server apply amortized away: a window of
    #: K contributors folded in ONE ``fused_update_batched`` launch
    #: bumps this by K - 1 (the coalescing contract — launches per
    #: round scale with shards, not shards x workers — is asserted on
    #: ``pallas_calls`` + this).
    apply_launches_saved: int = 0
    #: Bytes actually shipped by version-delta pulls (changed shard
    #: regions only; a full-snapshot fallback counts its full size).
    delta_bytes_tx: int = 0
    #: Bytes a full ``pull_packed`` snapshot would have shipped minus
    #: what the delta actually shipped — the tentpole's "bytes
    #: proportional to change" win, directly benchmarkable.
    full_pull_bytes_avoided: int = 0
    #: Live-reshard accounting (``repro.ft.reshard``): contributions
    #: parked against a mid-migration shard, contributions replayed
    #: onto the new shards after the swap, and whole pushes translated
    #: from a stale epoch's layout.  Zero-loss is asserted as
    #: ``reshard_parked == reshard_replayed`` once a migration settles.
    reshard_parked: int = 0
    reshard_replayed: int = 0
    reshard_translated: int = 0
    #: Parameter pulls a worker made (threaded or process worker).
    pulls: int = 0
    #: Bytes ``ShardedParameterServer.pull_packed`` wrote assembling
    #: the shard buffers into one wire on a cache miss (0 on a hit).
    pull_assemble_bytes: int = 0
    #: Bytes of host wire buffers uploaded to the device by a pull: the
    #: delta puller's patched buffer, a process worker's pulled wire.
    h2d_bytes: int = 0


#: Process-global counters — reset + snapshot around the region of
#: interest (see ``benchmarks/push_pull_latency.py``).
WIRE = HotPathCounters()


@dataclasses.dataclass
class TransportCounters(_CounterBase):
    """Bytes-on-the-wire accounting for the frame codec + transports.

    Bumped at the ``repro.wireformat`` encode/decode boundary, so every
    backend (tcp, shmem, the in-memory loopback) is counted the same
    way.  ``header_rejects`` counts frames refused by header validation
    (bad magic/version/dtype, length mismatch, truncation) — the
    failure-path tests and the throughput benchmark read it.
    Per-process like ``WIRE``: a worker process has its own counters.
    """

    frames_tx: int = 0
    frames_rx: int = 0
    bytes_tx: int = 0
    bytes_rx: int = 0
    header_rejects: int = 0


#: Process-global transport counters (see ``repro.wireformat``).
TRANSPORT = TransportCounters()


@dataclasses.dataclass
class KernelPathCounters(_CounterBase):
    """Which backward each traced attention step compiled: bumped once
    per trace of ``registry.attention_bwd`` (not per execution), by the
    path its shapes chose."""

    #: the flash backward kernels (``flash_attention_bwd``)
    attn_bwd_flash: int = 0
    #: ``jax.vjp`` of the ``ref.py`` oracle (shapes the kernel does not
    #: cover)
    attn_bwd_oracle: int = 0


#: Process-global kernel-path counters (see ``repro.kernels.registry``).
KERNELS = KernelPathCounters()


@dataclasses.dataclass
class MoECounters(_CounterBase):
    """What the held-expert layers routed, summed over layers and steps:
    the model's ``counts["moe"]``, which a worker step returns with its
    loss (``add_counts``), so counting costs no device sync of its own."""

    #: assignments the router sent to experts this chip holds
    held_rows: int = 0
    #: tokens the routers saw (one per token and expert layer)
    tokens_routed: int = 0
    #: the busiest held expert's rows, summed over layer-steps
    held_rows_max: int = 0


#: Process-global expert-routing counters (see ``add_counts``).
MOE = MoECounters()

#: Every process-global counter group by name.
GROUPS: Dict[str, _CounterBase] = {"wire": WIRE, "transport": TRANSPORT,
                                   "kernels": KERNELS, "moe": MOE}


def add_counts(counts: Mapping[str, Mapping[str, Any]]) -> None:
    """Bump the groups a model step counted, ``{group: {field: n}}`` on
    the host (a training step's ``counts`` aux)."""
    for name, group in counts.items():
        GROUPS[name].add(group)


def snapshot_all() -> Dict[str, Dict[str, int]]:
    """One combined view of every process-global counter group —
    ``session.metrics()`` and the obs metrics snapshots both read this
    instead of enumerating the globals themselves."""
    return {name: c.snapshot() for name, c in GROUPS.items()}
