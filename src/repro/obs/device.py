"""Device-side names for a trace: phase scopes in the jitted code, and
the op paths of the compiled programs a device trace is joined to.

The jax-side part of ``repro.obs`` (the rest is stdlib-only, and
nothing there imports this module).

* ``phase(name)`` is ``jax.named_scope`` over one of
  ``trace.PHASES``.  It is compile-time metadata on the ops'
  ``op_name``, so it costs nothing at run time.
* While ``TRACE`` is armed, this module holds the process's loaded XLA
  executables.  It takes them at ``enable``, when every program of a
  warmed-up run is loaded, and keeps them until ``op_paths()`` reads
  them or ``TRACE`` is next armed.  ``op_paths()`` maps each
  instruction of their optimized HLO, by the ``%name = shape`` head a
  device trace's op name starts with, to the instruction's op path.
  So a device trace can be folded by phase after the run that made it
  has freed its programs.
"""

from __future__ import annotations

import re
from typing import Dict, List

import jax

from repro.obs.trace import PHASES, TRACE

_HELD: List = []

#: ``[ROOT ]%name = ...`` lines of an HLO module's text.
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%\S+ = .*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def phase(name: str):
    """The named scope of device phase ``name`` (one of ``PHASES``)."""
    if name not in PHASES:
        raise ValueError(f"unknown device phase {name!r}; one of {PHASES}")
    return jax.named_scope(name)


def _on_trace(event: str) -> None:
    if event != "enable":
        return
    from jax.extend import backend
    try:
        _HELD[:] = backend.get_backend().live_executables()
    except Exception:  # a backend that cannot list them: no names
        _HELD[:] = []


TRACE.add_hook(_on_trace)


def op_head(text: str) -> str:
    """``%name = shape`` of an HLO instruction line, or of the name a
    device trace gives the op (which also spells out operand shapes):
    the key a trace is joined on."""
    text = text.strip()
    if text.startswith("ROOT "):
        text = text[5:]
    return " ".join(text.split(" ", 3)[:3])


def _operands(text: str) -> List[str]:
    """The instructions an instruction (``%x = shape opcode(...)``)
    reads, by name."""
    rest = text.partition(" = ")[2]
    i = _closing(rest, 0) + 1 if rest.startswith("(") else rest.find(" ")
    j = rest.find("(", max(i, 0))
    if i < 0 or j < 0:
        return []
    return re.findall(r"%[\w.\-]+", rest[j:_closing(rest, j) + 1])


def _closing(text: str, start: int) -> int:
    """Index of the bracket closing the one at ``start``."""
    depth = 0
    for k in range(start, len(text)):
        if text[k] in "([{":
            depth += 1
        elif text[k] in ")]}":
            depth -= 1
            if depth == 0:
                return k
    return len(text) - 1


def module_op_paths(hlo_text: str, module: str) -> Dict[str, str]:
    """Instruction (by ``op_head``) -> op path, for one module's text.  An instruction XLA made without an ``op_name`` (a
    layout copy, a buffer update) takes the path of the first operand
    that has one, else of the first instruction reading it, else its
    program's, ``jit(<function>)``, from the module name.

    A custom call XLA names after itself (the ``ragged-dot-none``
    kernels it expands ``jax.lax.ragged_dot`` into, whose first operands
    are routing metadata) takes the first path in a device phase among
    the instructions reading it, then among its operands from the last
    (a backward's incoming gradient) to the first."""
    root = f"jit({module[4:]})" if module.startswith("jit_") else module
    entries = []
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        path = op.group(1) if op and op.group(1).startswith("jit(") else None
        by_xla = bool(op) and path is None and " custom-call(" in line
        entries.append((op_head(line), m.group(1).split(" ", 1)[0],
                        path, _operands(m.group(1)), by_xla))
    known = {name: path for _, name, path, _, _ in entries if path}
    users: Dict[str, List[str]] = {}
    for _, name, path, operands, by_xla in entries:
        for o in operands:
            users.setdefault(o, []).append(name)
        if name not in known and not by_xla:
            got = next((known[o] for o in operands if o in known), None)
            if got:
                known[name] = got
    for _, name, _, operands, by_xla in entries:
        if by_xla:
            near = users.get(name, []) + operands[::-1]
            got = next((known[n] for n in near
                        if _in_phase(known.get(n))), None)
            if got:
                known[name] = got
    for _, name, _, _, _ in reversed(entries):
        if name not in known:
            got = next((known[u] for u in users.get(name, ())
                        if u in known), None)
            if got:
                known[name] = got
    return {key: known.get(name, root) for key, name, _, _, _ in entries}


def _in_phase(path) -> bool:
    return bool(path) and any(w in PHASES for w in re.findall(r"\w+", path))


def op_paths() -> Dict[str, str]:
    """Op paths of every instruction of the executables held since
    ``TRACE`` was last armed, by ``op_head``; releases them.  A head two
    programs share with different paths maps to ``""`` (unknown).
    Empty if none are held."""
    held, _HELD[:] = list(_HELD), []
    out: Dict[str, str] = {}
    for ex in held:
        try:
            mods = ex.hlo_modules()
        except Exception:  # an executable without its HLO is left out
            continue
        for mod in mods:
            for head, path in module_op_paths(mod.to_string(),
                                              mod.name).items():
                out[head] = path if out.get(head, path) == path else ""
    return out
