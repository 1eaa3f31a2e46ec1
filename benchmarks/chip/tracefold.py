"""Reduce a profiler trace to the numbers the per-layer metrics read.

``events_from_xspace`` turns the profiler's ``.xplane.pb`` into plain
event dicts; everything after that works on those dicts, so the tests
run on a small recorded excerpt (``testdata/trace_excerpt.json``).

An event is ``{"plane", "line", "name", "ts", "dur"}`` with times in
nanoseconds on the profiler's clock.  Device operations are the events
on a ``/device:TPU:<n>`` plane's ``XLA Ops`` line, each named by its HLO
instruction text (``%fusion.12 = bf16[...] fusion(...)``); a ``while``
loop's event spans the events of its body.  Pallas kernels are
``custom-call``s with ``custom_call_target="tpu_custom_call"``, named by
the jitted function around them or by the call site.  Host spans are on
``/host:CPU``.
"""

from __future__ import annotations

import collections
import json
import re
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
DEVICE_OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
ANCHOR = "bench_anchor"
#: Control-flow ops whose event spans the ops of their body.
CONTAINER = re.compile(r"^%(while|conditional|call)[\w.]* = ")


def events_from_xspace(path: str) -> List[Dict]:
    """Device ops of every TPU plane, and host events, as dicts."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        dev = DEVICE_PLANE.match(plane.name)
        if not dev and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if dev and line.name != DEVICE_OPS_LINE:
                continue
            for e in line.events:
                out.append({"plane": plane.name, "line": line.name,
                            "name": e.name, "ts": float(e.start_ns),
                            "dur": float(e.duration_ns)})
    return out


def load_excerpt(path: str) -> List[Dict]:
    with open(path) as f:
        return json.load(f)["events"]


def anchor_ns(events: Iterable[Dict]) -> float:
    """Profiler-clock time of the harness's ``bench_anchor`` annotation."""
    for e in events:
        if e["name"] == ANCHOR and e["plane"] == HOST_PLANE:
            return e["ts"]
    raise ValueError("the trace holds no bench_anchor annotation")


def device_ops(events: Iterable[Dict],
               window: Tuple[float, float]) -> Dict[str, List[Tuple]]:
    """plane -> [(name, start, end)] of device ops, clipped to the
    window; ops wholly outside it are dropped."""
    lo, hi = window
    per: Dict[str, List[Tuple]] = collections.defaultdict(list)
    for e in events:
        if not DEVICE_PLANE.match(e["plane"]) or \
                e["line"] != DEVICE_OPS_LINE:
            continue
        s, t = max(e["ts"], lo), min(e["ts"] + e["dur"], hi)
        if t > s:
            per[e["plane"]].append((e["name"], s, t))
    return per


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple]:
    """Merged, sorted, disjoint intervals."""
    out: List[List[float]] = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [tuple(x) for x in out]


def busy_seconds(ops: Dict[str, List[Tuple]], n_chips: int) -> float:
    """Seconds some op ran, per chip, averaged over the chips used."""
    total = sum(t - s for plane in ops.values()
                for s, t in union((s, t) for _, s, t in plane))
    return total / 1e9 / max(1, n_chips)


def label(hlo: str) -> str:
    """A short name for an op: its instruction name, output shape and,
    for a custom call, its target."""
    head, _, rest = hlo.partition(" = ")
    shape = re.match(r"\(?\w+\[[\d,]*\]", rest)
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    return " ".join(x for x in (
        head, shape.group(0).lstrip("(") if shape else "",
        target.group(1) if target else "") if x)


def op_seconds(ops: Dict[str, List[Tuple]]) -> Dict[str, float]:
    """Summed device time by op label, over every chip; loop containers
    are left out (their body's ops are counted)."""
    acc: Dict[str, float] = collections.defaultdict(float)
    for plane in ops.values():
        for name, s, t in plane:
            if not CONTAINER.match(name):
                acc[label(name)] += (t - s) / 1e9
    return dict(acc)


def matching(ops: Dict[str, List[Tuple]], pattern: str) -> Tuple[int, float]:
    """(count, summed seconds) of device ops whose name matches."""
    rx = re.compile(pattern)
    n, secs = 0, 0.0
    for plane in ops.values():
        for name, s, t in plane:
            if rx.search(name):
                n += 1
                secs += (t - s) / 1e9
    return n, secs


def gaps(ops: Dict[str, List[Tuple]],
         window: Tuple[float, float]) -> List[Tuple[float, float]]:
    """Idle intervals of every chip inside the window, longest first."""
    out = []
    for plane in ops.values():
        busy = union((s, t) for _, s, t in plane)
        edge = window[0]
        for s, t in busy:
            if s > edge:
                out.append((edge, s))
            edge = max(edge, t)
        if window[1] > edge:
            out.append((edge, window[1]))
    return sorted(out, key=lambda g: g[0] - g[1])


def host_activity(spans: Sequence[Dict], start: float, end: float) -> str:
    """The ``repro.obs`` spans (already on the profiler clock) that
    cover most of [start, end], by name; 'host:none' if none does."""
    cover: Dict[str, float] = collections.defaultdict(float)
    for sp in spans:
        s, t = max(sp["ts"], start), min(sp["ts"] + sp["dur"], end)
        if t > s:
            cover[sp["name"]] += t - s
    if not cover:
        return "host:none"
    top = sorted(cover.items(), key=lambda kv: -kv[1])[:2]
    return "+".join(name for name, _ in top)


def breakdown(ops: Dict[str, List[Tuple]], window: Tuple[float, float],
              spans: Sequence[Dict], top: int = 10) -> Dict[str, List]:
    """The device ops that took most time, and the longest idle gaps
    named by what the host was doing in them."""
    by_name = sorted(op_seconds(ops).items(), key=lambda kv: -kv[1])
    idle = [[f"{host_activity(spans, s, t)}", (t - s) / 1e9]
            for s, t in gaps(ops, window)[:top]]
    return {"device_ops": [[n, s] for n, s in by_name[:top]],
            "idle_gaps": idle}


def spans_on_profile_clock(spans: Sequence[Dict], anchor_pc: float,
                           anchor_ts: float, anchor_profile_ns: float,
                           ) -> List[Dict]:
    """Map drained ``repro.obs`` spans (wall-clock ``ts`` in seconds,
    anchored at ``enable``) onto the profiler clock, given one span
    recorded at perf-counter ``anchor_pc`` (drained as ``anchor_ts``)
    and the profiler time of the annotation made at that instant."""
    out = []
    for sp in spans:
        pc = sp["ts"] - anchor_ts + anchor_pc
        out.append({"name": sp["name"],
                    "ts": anchor_profile_ns + (pc - anchor_pc) * 1e9,
                    "dur": sp["dur"] * 1e9})
    return out
