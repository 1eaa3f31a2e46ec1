"""Gigabytes a pull copies on the device, per pull: the sharded
server's shard concat (``pull_assemble_bytes``, on a cache miss) and the
host buffers a pull uploads (``h2d_bytes``), over the pulls workers made,
from the program's hot-path counters over the traced interval
(``TRACE.window_counters``).  Bytes and pulls are bumped at the same
pull, so the ratio holds whole pulls however the interval ends."""


def read(ctx):
    from repro.obs.trace import TRACE
    window = getattr(TRACE, "window_counters", None)
    wire = window().get("wire", {}) if window else {}
    pulls = wire.get("pulls", 0)
    if pulls <= 0 or "pull_assemble_bytes" not in wire:
        return None
    copied = wire["pull_assemble_bytes"] + wire.get("h2d_bytes", 0)
    return {"value": copied / pulls / 1e9, "pulls": pulls}
