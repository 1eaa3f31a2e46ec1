"""The two kinds of cell: the threaded parameter server (``ps-threads``)
and the SPMD delayed-gradient trainer (``spmd``).

Each cell drives the program's own loop through its public entry
points, ``repro.api.build_session`` and ``session.run``, and reads its
counters.  Set-up builds the one session, drives it from the seed
through its first ``CHECK_STEPS`` steps with readings taken in between,
and hands that same session to the measured window.  After the window
the program is freed and the reference replays those first steps.

The model is the configuration's family: ``families/<family>.py``,
named by the file's ``family`` key (``dense`` where it names none),
which gives the sizes, the weights, the float32 reference and the
counts the readers divide by.
"""

from __future__ import annotations

import gc
import importlib.util
import threading
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chip import feed as feedlib
from chip import reference as ref

HERE = Path(__file__).resolve().parent
#: Steps (rounds, on the parameter server: one push of every worker)
#: the reference follows.
CHECK_STEPS = 3
#: Pushes of every worker before the parameter-server window opens: the
#: held rounds, then two free ones with every program compiled.
WARM_PUSHES = CHECK_STEPS + 3
#: Longest a parameter-server cell may go without a push before the run
#: is stopped: the first push compiles the step and the apply.
STALL_S = 600.0
#: Leaves whose reference gradient is under this share of the median
#: leaf's are left out of the change comparison (nought to rounding).
TINY_GRAD = 1e-3


#: The family of a configuration file that names none.
DEFAULT_FAMILY = "dense"
_FAMILIES: Dict[Path, Any] = {}


def load_family(name: str, here: Path = HERE):
    """The model family ``families/<name>.py`` under ``here``, loaded
    once a process.  It gives ``sizes(cfg)`` (the file's sizes the cell
    and the readers use; ``vocab_size`` among them, below which the feed
    draws tokens), ``program_sizes(program_config)`` (those the
    registry's ``ModelConfig`` states, by the same keys),
    ``init_params(m, key, dtype, *, eager)`` (the weights in the
    program's tree), ``loss_and_grad(m, params, tokens, labels, prec,
    with_grad, half)`` (the float32 reference, ``prec='fp8'`` the
    control, ``half`` the half-batch fault), ``param_count(m)``,
    ``train_flops_per_token(m, seq)``, and ``SMOKE``: the registry
    ``arch`` whose smoke configuration its ``config`` sizes are, with
    ``param_count`` and ``train_flops_per_token`` ({seq: FLOPs}) worked
    out by hand at them."""
    path = (here / "families" / f"{name}.py").resolve()
    if path not in _FAMILIES:
        spec = importlib.util.spec_from_file_location(
            f"chip_family_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _FAMILIES[path] = mod
    return _FAMILIES[path]


def family_of(cfg: Dict, here: Path = HERE):
    """The family the configuration file names."""
    return load_family(cfg.get("family", DEFAULT_FAMILY), here)


def check_program_config(cfg: Dict, family) -> Any:
    """The program's registry entry for the configuration, after
    checking that it has the file's sizes (those the registry states)."""
    from repro.configs import get_config, get_smoke_config
    prog = cfg["program"]
    c = (get_smoke_config(prog["arch"]) if prog.get("smoke")
         else get_config(prog["arch"]))
    have = dict(family.program_sizes(c), torch_dtype=c.dtype)
    want = dict(family.sizes(cfg), torch_dtype=cfg["torch_dtype"])
    bad = {k: (have[k], want.get(k)) for k in have if have[k] != want.get(k)}
    if bad:
        raise ValueError(f"{prog['arch']} does not have the sizes of the "
                         f"configuration file: {bad} (program, file)")
    return c


def leaf_norms_of(sumsq: List[float]) -> np.ndarray:
    return np.sqrt(np.asarray(sumsq, np.float64))


def worst_leaf_gap(prog: np.ndarray, want: np.ndarray,
                   keep: Optional[np.ndarray] = None) -> float:
    """Worst leaf's |program norm - reference norm| over the larger of
    that leaf's reference norm and the median leaf's."""
    keep = np.ones(len(want), bool) if keep is None else keep
    med = float(np.median(want[keep]))
    den = np.maximum(want, med)
    gaps = np.abs(prog - want) / np.where(den > 0, den, 1.0)
    return float(np.max(gaps[keep]))


def _sumsq(x) -> float:
    """Sum of squares of a host array, accumulated in float32 blocks."""
    x = np.asarray(x, np.float32).ravel()
    return float(sum(np.dot(x[i:i + 2**24], x[i:i + 2**24])
                     for i in range(0, x.size, 2**24)))


def _tree_sumsq(tree) -> List[float]:
    return [_sumsq(x) for x in jax.tree_util.tree_leaves(tree)]


def _tree_diff_sumsq(a, b) -> List[float]:
    """Per leaf, of a - b; both are copied to the host first, so nothing
    new is allocated on the chip."""
    return [_sumsq(np.asarray(x, np.float32) - np.asarray(y, np.float32))
            for x, y in zip(jax.tree_util.tree_leaves(a),
                            jax.tree_util.tree_leaves(b))]


def to_host(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


class CompileCounter:
    """Counts the compilations JAX reports while ``active``.  One per
    run: JAX keeps every listener for the life of the process."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.active and event in self.EVENTS:
            self.count += 1


class Cell:
    """What both engines share: the family and its sizes, seeds, the
    window's counters."""

    def __init__(self, cfg: Dict, traffic: Dict, seed: int, family=None):
        self.cfg, self.traffic = cfg, traffic
        self.family = family or family_of(cfg)
        self.m = self.family.sizes(cfg)
        self.seeds = feedlib.sub_seeds(seed)
        self.seq = int(traffic["seq_len"])
        self.batch = int(traffic["batch"])
        self.workers = int(traffic.get("workers", 1))
        self.chain = feedlib.MarkovLM(self.m["vocab_size"], self.seq,
                                      self.seeds["data"])
        self.tokens_per_step = self.batch * self.seq
        self.readings: Dict[str, Any] = {}
        #: (monotonic time, loss) of every step, for time to a loss
        self.trajectory: List[tuple] = []

    @property
    def store_dtype(self):
        """The parameters' dtype, in which the weights are served and
        the parameter server keeps its state."""
        return jnp.dtype(self.cfg["torch_dtype"])


# ===================================================================
# parameter server
# ===================================================================
class PSCell(Cell):
    engine = "ps-threads"

    def spec(self):
        from repro import api
        c, srv = self.cfg, self.cfg["server"]
        opt, sync = c["optimizer"], c["sync"]
        return api.RunSpec(
            model=api.ModelSpec(arch=c["program"]["arch"],
                                smoke=bool(c["program"].get("smoke"))),
            data=api.DataSpec(seq_len=self.seq, global_batch=self.batch,
                              seed=self.seeds["data"]),
            optimizer=api.OptimizerSpec(
                lr=opt["lr"], momentum=opt["momentum"],
                staleness_damping=opt["staleness_damping"]),
            sync=api.SyncSpec(mode=sync["mode"], s_lower=sync["s_lower"],
                              s_upper=sync["s_upper"]),
            ps=api.ServerSpec(kind="sharded", shards=srv["shards"],
                              workers=self.workers, apply=srv["apply"],
                              gating=srv["gating"]),
            wire=api.WireSpec(format=c["wire"]["format"],
                              delta_pull=c["wire"]["delta_pull"]))

    def weights(self):
        return self.family.init_params(self.m, jax.random.PRNGKey(
            self.seeds["weights"]), self.store_dtype)

    # -- set-up --------------------------------------------------------
    def setup(self) -> None:
        from repro import api
        self.losses: Dict[int, List[float]] = {
            w: [] for w in range(self.workers)}
        self.orders: Dict[int, List[List[int]]] = {}
        self.feed = feedlib.Feed(
            self.chain, self.workers, self.batch, hold=CHECK_STEPS + 1,
            to_device=lambda b: {k: jnp.asarray(v) for k, v in b.items()},
            on_hold=self._at_hold)
        params = self.weights()
        self.p0 = to_host(params)      # the readings run on the host
        self.session = api.build_session(
            self.spec(), params=params, batches=self.feed,
            speed_factors=list(self.traffic["speed_factors"]),
            loss_from_aux=self._loss_from_aux).start()
        del params
        self.server = self.session.server
        self.plan = self.server.plan
        self.failure: List[BaseException] = []
        self.runner = threading.Thread(target=self._run, daemon=True,
                                       name="bench-session")
        self.runner.start()
        self._wait(lambda: min(self._pushes()) >= WARM_PUSHES)

    def _run(self) -> None:
        try:
            self.session.run(10**9)
        except BaseException as e:  # re-raised by the main thread
            self.failure.append(e)

    def _wait(self, cond: Callable[[], bool]) -> None:
        """Poll until ``cond``; raise the session's failure, or stop
        when no push has landed for ``STALL_S`` (a worker that died
        leaves its peer running alone, so the session never returns)."""
        last, since = -1, time.monotonic()
        while not cond():
            if self.feed.error is not None:
                raise self.feed.error
            if self.failure:
                raise self.failure[0]
            if not self.runner.is_alive():
                raise RuntimeError("the session stopped before the window")
            n = self.server.metrics.total_pushes
            if n != last:
                last, since = n, time.monotonic()
            elif time.monotonic() - since > STALL_S:
                raise TimeoutError(f"no push for {STALL_S} s")
            time.sleep(0.002)

    def _pushes(self) -> List[int]:
        p = dict(self.server.metrics.pushes)
        return [p.get(w, 0) for w in range(self.workers)]

    def _loss_from_aux(self, aux) -> float:
        loss = float(aux["loss"])
        w = int(threading.current_thread().name.rsplit("-", 1)[1])
        if len(self.losses[w]) < CHECK_STEPS:
            self.losses[w].append(loss)
        self.trajectory.append((time.monotonic(), loss))
        return loss

    def _at_hold(self, k: int) -> None:
        """Every worker waits for batch ``k``: rounds 1..k are applied in
        full and nothing moves.  Read the arrival order of round k on
        every shard, and the state the comparison needs."""
        if k == 0:
            return
        from repro.ft import snapshot_server
        tree, extras = snapshot_server(self.server)
        counts = [sorted(set(s["tracker"]["counts"].values()))
                  for s in extras["shards"]]
        if any(c != [k] for c in counts):
            raise RuntimeError(f"server not quiescent at round {k}: "
                               f"push counts per shard {counts}")
        self.orders[k] = [
            sorted(range(self.workers),
                   key=lambda w: s["tracker"]["table"][str(w)][0])
            for s in extras["shards"]]
        bufs = [tree[f"shard{j:03d}"] for j in range(self.plan.n_shards)]
        if k == 1:
            self.readings["grad1"] = self._per_leaf(
                [np.asarray(b["m"]) for b in bufs],
                lambda piece, j, i: piece)
        if k == CHECK_STEPS:
            pieces0 = self.plan.split(self.p0)
            self.readings["change"] = self._per_leaf(
                [np.asarray(b["p"]) for b in bufs],
                lambda piece, j, i: piece.astype(np.float32)
                - pieces0[j][i].astype(np.float32))

    def _per_leaf(self, shard_bufs, fn) -> np.ndarray:
        """Per-leaf norms of ``fn(piece)`` over the pieces each shard's
        host copy holds (a leaf may be split over shards)."""
        sumsq = [0.0] * len(self.plan.leaf_shapes)
        for j, buf in enumerate(shard_bufs):
            pieces = self.plan.shard_pieces_from_wire(buf, j)
            for i, (sl, piece) in enumerate(
                    zip(self.plan.shards[j].slices, pieces)):
                sumsq[sl.leaf] += _sumsq(fn(piece, j, i))
        return leaf_norms_of(sumsq)

    # -- window --------------------------------------------------------
    def counters(self) -> Dict[str, Any]:
        """The server's counters at the moment the next push completes:
        a window between two completions counts whole pushes only."""
        applied = self.server.metrics.applied_updates
        self._wait(lambda: self.server.metrics.applied_updates != applied)
        m = self.server.metrics
        return {"t": time.monotonic(), "applied": m.applied_updates,
                "hist": dict(m.staleness_hist),
                "wait": sum(dict(m.wait_time).values())}

    def window_numbers(self, a: Dict, b: Dict) -> Dict[str, float]:
        secs = b["t"] - a["t"]
        hist = {s: b["hist"].get(s, 0) - a["hist"].get(s, 0)
                for s in b["hist"]}
        n = sum(hist.values())
        return {
            "seconds": secs,
            "tokens": (b["applied"] - a["applied"]) * self.tokens_per_step,
            "pushes": n,
            "staleness_mean": (sum(s * c for s, c in hist.items()) / n
                               if n else float("nan")),
            "wait_s": b["wait"] - a["wait"],
        }

    # -- end -------------------------------------------------------------
    def finish(self) -> Dict[str, Any]:
        """Stop the session and hold the gate to its guarantee: every
        push counted on every shard, none over the DSSP bound."""
        self.server.stop()
        self.feed.close()
        self.runner.join(timeout=300)
        if self.runner.is_alive():
            raise TimeoutError("the session did not stop")
        if self.failure:
            raise self.failure[0]
        m, srv = self.server.metrics, self.server
        total = m.total_pushes
        s_upper = self.cfg["sync"]["s_upper"]
        checks = {
            "lost_pushes": (abs(sum(srv.shard_versions())
                                - srv.n_shards * total)
                            + abs(m.applied_updates - total)
                            + abs(sum(m.pushes.values()) - total), 0),
            "over_bound": (max(0, m.max_staleness - (s_upper + 1)), 0),
        }
        self.session.close()
        self.session = self.server = self.runner = self.feed = None
        gc.collect()
        return checks

    # -- reference -------------------------------------------------------
    def reference(self) -> Dict[str, float]:
        """Replay the first rounds in the order the server recorded them
        on each shard, and compare."""
        pieces: Dict[int, List] = {}
        for sh in self.plan.shards:
            for sl in sh.slices:
                pieces.setdefault(sl.leaf, []).append(
                    (sl.start, sl.stop, sh.index))
        losses, grad1, change = ps_replay(
            self, lambda k, leaf: [(a, b, self.orders[k][j])
                                   for a, b, j in pieces[leaf]])
        if any(len(self.losses[w]) != CHECK_STEPS for w in losses):
            return {"loss_gap": float("inf"), "grad1_gap": float("inf"),
                    "change_gap": float("inf")}
        return compare(
            [x for w in sorted(losses) for x in self.losses[w]],
            [x for w in sorted(losses) for x in losses[w]],
            (self.readings["grad1"], grad1),
            (self.readings["change"], change))


def compare(losses_prog, losses_ref, grad1, change) -> Dict[str, float]:
    """The numbers compared: the largest step-loss gap, and the worst
    leaf's gap in the first gradient's norm and in the change's norm
    (leaves whose reference gradient is nought to rounding left out of
    the change)."""
    keep = grad1[1] >= TINY_GRAD * float(np.median(grad1[1]))
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(losses_prog,
                                                  losses_ref)),
        "grad1_gap": worst_leaf_gap(*grad1),
        "change_gap": worst_leaf_gap(*change, keep),
    }


def ps_replay(cell: "PSCell", order_of: Callable, prec: str = "f32",
              half: bool = False):
    """The first ``CHECK_STEPS`` rounds of a parameter-server cell in the
    reference: every worker's loss and gradient on the state its pull
    saw (the state after the previous round), then each leaf piece
    updated in the order ``order_of(round, leaf)`` gives as
    ``[(start, stop, workers in order)]``.  Returns (losses per worker,
    per-leaf norms of the momentum after round 1, per-leaf norms of the
    parameters' change after the last round)."""
    opt = cell.cfg["optimizer"]
    lr, beta = float(opt["lr"]), float(opt["momentum"])
    store = cell.store_dtype    # the server keeps p and m in the wire dtype
    p = cell.weights()
    p0 = to_host(p)
    mom = jax.tree_util.tree_map(jnp.zeros_like, p)
    treedef = jax.tree_util.tree_structure(p)
    losses = {w: [] for w in range(cell.workers)}
    grad1 = None
    for k in range(1, CHECK_STEPS + 1):
        grads = []     # on the host: the chip holds one gradient at a time
        for w in range(cell.workers):
            b = feedlib.tokens(cell.chain, k - 1,
                               feedlib.worker_rows(w, cell.batch))
            loss, g = cell.family.loss_and_grad(
                cell.m, p, b["tokens"], b["labels"], prec=prec, half=half)
            losses[w].append(loss)
            grads.append([np.asarray(x) for x in
                          jax.tree_util.tree_leaves(g)])
            del g
        ps, ms = jax.tree_util.tree_leaves(p), jax.tree_util.tree_leaves(mom)
        del p, mom
        for li in range(len(ps)):
            for start, stop, order in order_of(k, li):
                stop = ps[li].shape[0] if stop is None else stop
                whole = start == 0 and stop == ps[li].shape[0]
                sl = slice(None) if whole else slice(start, stop)
                pp, mm = ps[li][sl], ms[li][sl]
                for w in order:
                    pp, mm = ref.momentum_apply(
                        pp, mm, jnp.asarray(grads[w][li][sl]), lr=lr,
                        beta=beta, scale=1.0, store=store)
                if whole:
                    ps[li], ms[li] = pp, mm
                else:
                    ps[li] = ps[li].at[sl].set(pp)
                    ms[li] = ms[li].at[sl].set(mm)
        p = jax.tree_util.tree_unflatten(treedef, ps)
        mom = jax.tree_util.tree_unflatten(treedef, ms)
        del grads, ps, ms
        if k == 1:
            grad1 = leaf_norms_of(_tree_sumsq(to_host(mom)))
    return losses, grad1, leaf_norms_of(_tree_diff_sumsq(to_host(p), p0))


# ===================================================================
# SPMD delayed-gradient trainer
# ===================================================================
class SpmdCell(Cell):
    engine = "spmd"

    def spec(self):
        from repro import api
        c, opt, sync = self.cfg, self.cfg["optimizer"], self.cfg["sync"]
        return api.RunSpec(
            model=api.ModelSpec(arch=c["program"]["arch"],
                                smoke=bool(c["program"].get("smoke"))),
            data=api.DataSpec(seq_len=self.seq, global_batch=self.batch,
                              seed=self.seeds["data"]),
            optimizer=api.OptimizerSpec(
                name=opt["name"], lr=opt["lr"],
                staleness_damping=opt["staleness_damping"]),
            sync=api.SyncSpec(mode=sync["mode"], s_lower=sync["s_lower"],
                              s_upper=sync["s_upper"]))

    def setup(self) -> None:
        from repro import api
        self.p0 = to_host(self.weights())
        self.session = api.build_session(self.spec()).start()
        tr = self.session.trainer
        self.grad1_step = None
        b1 = float(self.cfg["optimizer"]["b1"])
        for t in range(CHECK_STEPS):
            self.session.run(1)
            if self.grad1_step is None and t >= tr.log.delays[t]:
                # the first step whose update carried a gradient
                self.grad1_step = t
                self.readings["grad1"] = leaf_norms_of(
                    _tree_sumsq(to_host(tr.opt_state.mu))) / (1.0 - b1)
        self.readings["change"] = leaf_norms_of(
            _tree_diff_sumsq(to_host(tr.params), self.p0))
        self.readings["losses"] = list(tr.log.losses[:CHECK_STEPS])
        self.readings["delays"] = list(tr.log.delays[:CHECK_STEPS])

    def weights(self):
        # The trainer makes its own weights, from a fixed key, op by op.
        return self.family.init_params(self.m, jax.random.PRNGKey(0),
                                       self.store_dtype, eager=True)

    def counters(self) -> Dict[str, Any]:
        return {"t": time.monotonic(),
                "steps": self.session.trainer.step_idx}

    def window_numbers(self, a: Dict, b: Dict) -> Dict[str, float]:
        return {"seconds": b["t"] - a["t"],
                "tokens": (b["steps"] - a["steps"]) * self.tokens_per_step}

    def drive(self, until: float) -> None:
        """The window's work: the trainer's own loop, ``steps_per_call``
        steps a call (each call sets up its data iterator), until the
        deadline passes; the window ends when the last call returns."""
        k = int(self.traffic.get("steps_per_call", 1))
        log = self.session.trainer.log
        while time.monotonic() < until:
            self.session.run(k)
            now = time.monotonic()
            self.trajectory.extend((now, x) for x in log.losses[-k:])

    def finish(self) -> Dict[str, Any]:
        self.session.close()
        self.session = None
        gc.collect()
        return {}

    def reference(self) -> Dict[str, float]:
        losses, grad1, change = spmd_replay(
            self, self.readings["delays"], self.grad1_step)
        return compare(self.readings["losses"], losses,
                       (self.readings["grad1"], grad1),
                       (self.readings["change"], change))


def spmd_replay(cell: "SpmdCell", delays: List[int], grad1_step: int,
                prec: str = "f32", half: bool = False):
    """The first ``CHECK_STEPS`` steps of the SPMD trainer in the
    reference: loss and gradient at every step, the gradient of
    ``delays[t]`` steps ago applied by AdamW with the staleness-damped
    first moment.  The moments live on the host, so the chip holds one
    parameter copy and one gradient.  Returns (losses, per-leaf norms of
    the first moment over (1 - b1) after step ``grad1_step``, per-leaf
    norms of the parameters' change).  ``prec`` and ``half`` as in
    ``ps_replay``."""
    opt, sync = cell.cfg["optimizer"], cell.cfg["sync"]
    b1 = float(opt["b1"])
    hyper = dict(lr=float(opt["lr"]), b1=b1, b2=float(opt["b2"]),
                 eps=float(opt["eps"]),
                 weight_decay=float(opt["weight_decay"]))
    depth = int(sync["s_upper"]) + 1
    p = cell.weights()
    p0 = to_host(p)
    treedef = jax.tree_util.tree_structure(p)
    shapes = [x.shape for x in jax.tree_util.tree_leaves(p)]
    mu = [np.zeros(s, np.float32) for s in shapes]
    nu = [np.zeros(s, np.float32) for s in shapes]
    ring: Dict[int, List[np.ndarray]] = {}
    losses, grad1 = [], None
    rows = list(range(cell.batch))
    for t in range(CHECK_STEPS):
        b = feedlib.tokens(cell.chain, t, rows)
        d = int(delays[t])
        need = t + 1 < CHECK_STEPS       # applied within the steps
        loss, g = cell.family.loss_and_grad(
            cell.m, p, b["tokens"], b["labels"], prec=prec,
            with_grad=need, half=half)
        losses.append(loss)
        if need:
            ring[t % depth] = [np.asarray(x) for x in
                               jax.tree_util.tree_leaves(g)]
            del g
        valid = t >= d
        g_app = ring.get((t - d) % depth) if valid else None
        scale = 1.0 / (1.0 + d) if opt["staleness_damping"] else 1.0
        ps = jax.tree_util.tree_leaves(p)
        del p
        for i in range(len(ps)):
            gi = (jnp.asarray(g_app[i]) if g_app is not None
                  else jnp.zeros((), jnp.float32))
            ps[i], m_i, v_i = ref.adamw_apply(
                ps[i], jnp.asarray(mu[i]), jnp.asarray(nu[i]), gi,
                jnp.int32(t + 1), jnp.float32(scale),
                jnp.float32(1.0 if valid else 0.0), **hyper)
            mu[i], nu[i] = np.asarray(m_i), np.asarray(v_i)
        p = jax.tree_util.tree_unflatten(treedef, ps)
        del ps
        if t == grad1_step:
            grad1 = leaf_norms_of([_sumsq(x) for x in mu]) / (1.0 - b1)
    return losses, grad1, leaf_norms_of(_tree_diff_sumsq(to_host(p), p0))


ENGINES = {"ps-threads": PSCell, "spmd": SpmdCell}
