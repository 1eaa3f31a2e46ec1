"""Fold a window's device time by program phase.

The program names its device work with ``jax.named_scope`` phases
(``repro.obs.trace.PHASES``): each op's ``op_name`` is a path such as
``jit(_packed_step)/transpose(jvp())/while/body/closed_call/checkpoint/
attn_fwd/attn_bwd/...``.  ``phase_of`` reads the phase off the path:

* a program that is one phase as a whole (``PHASE_PROGRAMS``, by the
  ``jit(<function>)`` that roots the path) gives its phase to every op;
* otherwise the innermost phase scope on the path; ``attn_fwd`` under a
  backward pass (a ``transpose(`` not undone by a later
  ``rematted_computation``) is the attention's backward, ``attn_bwd``;
* otherwise ``bwd`` under a backward pass, ``fwd`` inside a
  differentiated function (``jvp(``), and ``unscoped`` for the rest.

The trace's op events do not carry the path (through
``jax.profiler.ProfileData`` a TPU op event holds only its device
offset, duration and time scale), so the path comes from the program's
compiled HLO (``repro.obs.device.op_paths``), joined on the
``%name = shape`` head of the op's name.  A program without those names (one
older than them) gives no fold, and the readers that need one report
nothing.
"""

from __future__ import annotations

import re
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from chip import tracefold


def split_path(path: str) -> List[str]:
    """The components of an op path: split on ``/`` outside brackets."""
    out, depth, start = [], 0, 0
    for i, ch in enumerate(path):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == "/" and depth == 0:
            out.append(path[start:i])
            start = i + 1
    out.append(path[start:])
    return [c for c in out if c]


def phase_of(path: str, phases: Iterable[str],
             programs: Dict[str, str]) -> str:
    """The phase of one op, from its op path (see the module doc)."""
    parts = split_path(path or "")
    root = re.fullmatch(r"jit\((.*)\)", parts[0]) if parts else None
    if root and root.group(1) in programs:
        return programs[root.group(1)]
    phases = set(phases)
    found, backward, differentiated = None, False, False
    for c in parts[1:]:
        words = re.findall(r"\w+", c)
        if c.startswith("transpose("):
            backward = True
        elif c == "rematted_computation":
            backward = False
        if c.startswith(("jvp(", "transpose(")):
            differentiated = True
        # a scope inside a transform's brackets, e.g. transpose(jvp(wire))
        hit = [w for w in words if w in phases]
        if hit:
            found = hit[-1]
    if found == "attn_fwd" and backward:
        return "attn_bwd"
    if found:
        return found
    if backward:
        return "bwd"
    return "fwd" if differentiated else "unscoped"


def fold(ops: Dict[str, List[Tuple]], path_of: Callable[[str], Optional[str]],
         phases: Iterable[str], programs: Dict[str, str],
         names: Iterable[str]) -> Dict[str, float]:
    """Device seconds per phase, summed over chips; every name in
    ``names`` is a key.  Loop containers are left out (their body's ops
    are counted), as in ``tracefold.op_seconds``.  An op whose path is
    unknown is ``unscoped``."""
    phases = tuple(phases)
    out = {n: 0.0 for n in names}
    memo: Dict[str, str] = {}
    for plane in ops.values():
        for name, s, t in plane:
            if tracefold.CONTAINER.match(name):
                continue
            ph = memo.get(name)
            if ph is None:
                path = path_of(name)
                ph = memo[name] = (phase_of(path, phases, programs)
                                   if path else "unscoped")
            out[ph] = out.get(ph, 0.0) + (t - s) / 1e9
    return out


def fold_ctx(ctx: Dict) -> Optional[Dict[str, float]]:
    """The traced window's phase seconds, from the program's own names
    (``repro.obs.device``), or None where the program has none.  Computed
    once per run and kept in ``ctx``; also added to ``ctx["breakdown"]``,
    the result's ``breakdown``, as ``phases``."""
    if "phase_seconds" in ctx:
        return ctx["phase_seconds"]
    ctx["phase_seconds"] = None
    try:
        from repro.obs import device
        from repro.obs.trace import FOLD_PHASES, PHASE_PROGRAMS, PHASES
    except ImportError:
        return None
    paths = device.op_paths()
    if not paths:
        return None
    got = fold(ctx["ops"], lambda name: paths.get(device.op_head(name)),
               PHASES, PHASE_PROGRAMS, FOLD_PHASES)
    ctx["phase_seconds"] = got
    ctx.setdefault("breakdown", {})["phases"] = got
    return got
