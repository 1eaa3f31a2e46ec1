"""Restartable out-of-process parameter server.

``ServerProcess`` hosts a ``build_server(spec)`` +
``PSServerEndpoint`` + ``TcpTransport`` stack in its *own* spawned
process, which is what makes killing it meaningful: SIGKILL takes out
the packed store, every live socket, and every in-flight push — the
honest failure model for a parameter-server machine dropping off the
fleet.

The failover loop the chaos tests (and a real deployment script)
drive:

    sp = ServerProcess(spec)         # spec.ft.dir names the ckpt dir
    addr = sp.start()                # fresh run: no snapshot to load
    ... workers train, snapshotter checkpoints every snapshot_every_s
    sp.kill()                        # SIGKILL — or the machine dies
    addr2 = sp.restart()             # same port, resumes from latest
                                     # snapshot; workers' reconnect
                                     # loops re-HELLO and full-resync

``restart`` rebinds the SAME host:port (``socket.create_server`` sets
SO_REUSEADDR on POSIX), so the address workers hold stays valid across
the failover — their backoff loop only has to outlast the restart.

tcp only: shmem segments die with the process that owns them, so a
killed shmem server takes the transport down unrecoverably (spec
validation enforces this).

In-process faults: ``spec.ft.fault_kill_server_round >= 0`` arms a
watchdog thread that SIGKILLs the server the moment its aggregate push
count crosses the round — deterministic in *round* (the paper's unit
of progress), not in wall-clock.  A restarted incarnation never
re-arms the watchdog.

The server-side trace ring spills to ``<trace_spill>/server<i>.jsonl``
on a short cadence, so ``snapshot_shard``/``snapshot`` spans survive
the SIGKILL and the parent's collector can still assert the
per-shard-pause bound after the chaos run.
"""

from __future__ import annotations

import json
import multiprocessing
import os
import signal
import threading
import time
from typing import Any, Dict, Optional, Tuple

from repro.launch.runtime import pin_child_platform

_SPILL_PERIOD_S = 0.2


def _spill_loop(trace, path: str, stop: threading.Event) -> None:
    with open(path, "a", encoding="utf-8") as fh:
        while True:
            stopped = stop.wait(_SPILL_PERIOD_S)
            for e in trace.drain():
                fh.write(json.dumps(e, separators=(",", ":")))
                fh.write("\n")
            fh.flush()
            if stopped:
                return


def _server_main(spec_dict: Dict[str, Any], port: int, queue,
                 trace_spill: str, kill_server_round: int,
                 incarnation: int) -> None:
    """Entry point of the spawned server process."""
    pin_child_platform()
    from repro.api.session import build_server
    from repro.api.spec import RunSpec
    from repro.checkpoint.manager import CheckpointManager
    from repro.ft.snapshot import ServerSnapshotter, restore_latest
    from repro.obs.trace import TRACE
    from repro.transport import PSServerEndpoint
    from repro.transport.tcp import TcpTransport

    spec = RunSpec.from_dict(spec_dict)
    spill_stop = threading.Event()
    if spec.obs.trace or trace_spill:
        TRACE.enable(source=f"server{incarnation}")
    if trace_spill:
        os.makedirs(trace_spill, exist_ok=True)
        threading.Thread(
            target=_spill_loop,
            args=(TRACE, os.path.join(trace_spill,
                                      f"server{incarnation}.jsonl"),
                  spill_stop),
            name="ft-trace-spill", daemon=True).start()

    server = build_server(spec)
    manager = CheckpointManager(spec.ft.dir, keep=spec.ft.keep)
    # Resume BEFORE serving: the endpoint's pull cache is keyed by
    # version, and a restore lowers versions — nothing may be served
    # from the pre-restore state.
    resumed_step = restore_latest(server, manager)
    endpoint = PSServerEndpoint(server)
    transport = TcpTransport(spec.transport.host, port)
    transport.serve(endpoint)

    snapshotter = None
    if spec.ft.snapshot_every_s > 0:
        snapshotter = ServerSnapshotter(
            server, manager, spec.ft.snapshot_every_s).start()

    if kill_server_round >= 0:
        def watchdog() -> None:  # pragma: no cover - dies via SIGKILL
            while server.metrics.total_pushes < kill_server_round:
                time.sleep(0.005)
            os.kill(os.getpid(), signal.SIGKILL)
        threading.Thread(target=watchdog, name="ft-kill-watchdog",
                         daemon=True).start()

    stop = threading.Event()
    if spec.ft.reshards:
        # Live-reshard trigger: manual (aggregate push round) and/or
        # the hot-shard policy (per-shard applied-update growth read
        # off the server's version vector — the obs per-shard push
        # metric).  Re-armed in EVERY incarnation: reshard() to an
        # arity the restore already reached is a no-op, and a restart
        # that resumed from a pre-migration snapshot gets to finish
        # the move.  The mid-migration SIGKILL fires once, in the
        # first incarnation only (mirrors the kill watchdog).
        armed_kill = (spec.ft.fault_kill_mid_reshard
                      and incarnation == 0)

        def _mid_hook(shard_index: int) -> None:
            # Fires after each old shard's state is copied out; dying
            # at index >= 1 leaves the migration genuinely mid-flight.
            if shard_index >= 1:  # pragma: no cover - dies via SIGKILL
                os.kill(os.getpid(), signal.SIGKILL)

        def reshard_trigger() -> None:
            target = spec.ft.reshard_shards
            round_ = spec.ft.reshard_round
            hot = spec.ft.reshard_hot_factor
            last = server.shard_versions()
            while not stop.is_set() and not server.stopped:
                time.sleep(0.02)
                if round_ >= 0 \
                        and server.metrics.total_pushes >= round_:
                    server.reshard(
                        target,
                        _mid_hook=_mid_hook if armed_kill else None)
                    return
                if hot > 0.0:
                    cur = server.shard_versions()
                    if len(cur) == len(last):
                        deltas = [c - b for c, b in zip(cur, last)]
                        total = sum(deltas)
                        if total > 0 and max(deltas) > \
                                hot * (total / len(deltas)):
                            server.reshard(target)
                            return
                    last = cur

        threading.Thread(target=reshard_trigger,
                         name="ft-reshard-trigger",
                         daemon=True).start()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    queue.put(("up", transport.address(), resumed_step))
    stop.wait()

    # Graceful shutdown: final snapshot, release gated pushes with a
    # STOP, tear the wire down, flush the trace spill.
    if snapshotter is not None:
        try:
            snapshotter.stop(final_save=True)
        except Exception:
            pass  # a torn final save must not block the shutdown
    server.stop()
    transport.shutdown()
    server.shutdown()
    spill_stop.set()
    time.sleep(2 * _SPILL_PERIOD_S)  # let the spill thread drain
    queue.put(("down", server.metrics.total_pushes, None))


class ServerProcess:
    """Parent-side handle on one spawned, restartable server."""

    def __init__(self, spec, *, port: int = 0, trace_spill: str = "",
                 mp_context: str = "spawn",
                 start_timeout: float = 120.0):
        self.spec = spec
        self.port = port            # 0 = ephemeral on first start
        self.trace_spill = trace_spill
        self.start_timeout = start_timeout
        self.incarnation = 0
        self.resumed_step: Optional[int] = None
        self.address: Optional[Tuple] = None
        self._ctx = multiprocessing.get_context(mp_context)
        self._queue = self._ctx.Queue()
        self._proc: Optional[multiprocessing.Process] = None

    # -- lifecycle ----------------------------------------------------
    def start(self) -> Tuple:
        """Spawn (or respawn) the server; blocks until it serves.
        Returns its transport address — stable across restarts."""
        if self._proc is not None and self._proc.is_alive():
            raise RuntimeError("server process already running")
        # The FaultPlan's server-kill only fires in the FIRST
        # incarnation: a restarted server must get to finish the run.
        kill_round = (self.spec.ft.fault_kill_server_round
                      if self.incarnation == 0 else -1)
        self._proc = self._ctx.Process(
            target=_server_main,
            args=(self.spec.to_dict(), self.port, self._queue,
                  self.trace_spill, kill_round, self.incarnation),
            name=f"ft-ps-server-{self.incarnation}", daemon=True)
        self._proc.start()
        deadline = time.monotonic() + self.start_timeout
        while True:
            try:
                tag, addr, resumed = self._queue.get(timeout=1.0)
            except Exception:
                if not self._proc.is_alive():
                    raise RuntimeError(
                        f"server process died during startup (exit "
                        f"{self._proc.exitcode})") from None
                if time.monotonic() > deadline:
                    raise RuntimeError("server startup timed out")
                continue
            if tag == "up":
                break
        self.address = addr
        self.resumed_step = resumed
        # Pin the ephemeral port the first bind chose so every restart
        # lands on the address the workers are retrying against.
        self.port = addr[2]
        self.incarnation += 1
        return addr

    def restart(self) -> Tuple:
        """Failover: reap the corpse, respawn on the same port (the new
        incarnation resumes from the latest snapshot in spec.ft.dir)."""
        if self._proc is not None:
            self._proc.join(timeout=10.0)
        return self.start()

    def is_alive(self) -> bool:
        return self._proc is not None and self._proc.is_alive()

    def wait_dead(self, timeout: float = 60.0) -> bool:
        """Block until the server process exits (a FaultPlan kill is
        asynchronous); False on timeout."""
        deadline = time.monotonic() + timeout
        while self.is_alive():
            if time.monotonic() > deadline:
                return False
            time.sleep(0.01)
        return True

    def kill(self) -> None:
        """SIGKILL — the crash case.  No flush, no final snapshot."""
        if self._proc is not None and self._proc.is_alive():
            self._proc.kill()
            self._proc.join(timeout=10.0)

    def stop(self) -> None:
        """SIGTERM — the graceful case: final snapshot, STOP replies to
        gated workers, clean socket teardown."""
        if self._proc is not None and self._proc.is_alive():
            self._proc.terminate()
            self._proc.join(timeout=30.0)

    def __enter__(self) -> "ServerProcess":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()
        self.kill()


__all__ = ["ServerProcess"]
