"""Reduction of a traced window's device time to the program's named
scopes (``repro.obs``: teacher, student, generator, loss).

A TPU trace names each device operation by its HLO text (``%fusion.12 =
f32[...] fusion(...)``) and carries no ``op_name``. The program keeps
its stage-2 chunk program (``repro.obs.keep_program``), so after the
window its compiled HLO text (``repro.obs.program_text``) maps each
instruction to its ``op_name``: the name stack of the jitted program,
for example
``jit(epochs_step)/while/body/.../transpose(jvp(teacher))/resnet18/conv``.
An operation belongs to the first component of that path that is a
scope once transformation wrappers (``jvp(..)``, ``transpose(..)``) are
taken off; a fused operation whose ``op_name`` joins several names with
``;`` belongs to the first name's scope, and an instruction the compiler
added without an ``op_name`` to its first operand's (``op_names``). Time
is self time, as ``Trace.op_seconds`` computes it, so an enclosing
``while`` is not counted twice. Operations with no scope (loop control,
random draws, copies of loop state, and the few ops of other programs in
the window) are ``None``'s.
"""
from __future__ import annotations

import re

PROGRAM = "dense.epochs_step"
_WRAPPED = re.compile(r"(?:\w+\()*([\w.-]+)\)*")
_INSTR = re.compile(r"^\s*(?:ROOT )?(%[^\s=]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%[\w.-]+")


def program_scopes():
    """The program's scope names, or None for a program without them."""
    try:
        from repro.obs import SCOPES
    except ImportError:
        return None
    return SCOPES


def op_names(hlo_text: str) -> dict:
    """``%instruction`` -> ``op_name`` for the instructions of an HLO
    module's text. An instruction the compiler added (a copy, the end of
    an async copy, a layout change) carries none; it takes the op name
    of its first operand that has one, so moving data is charged to the
    scope that made it."""
    own, operands = {}, {}
    for name, rest in _INSTR.findall(hlo_text):
        m = _OP_NAME.search(rest)
        if m:
            own[name] = m.group(1)
        else:
            args = rest.split("(", 1)[-1].split("), ", 1)[0]
            operands[name] = _OPERAND.findall(args)

    none = set()

    def inherited(name, depth=0):
        if name in own or name in none:
            return own.get(name, "")
        if depth < 32:
            for o in operands.get(name, ()):
                found = inherited(o, depth + 1)
                if found:
                    own[name] = found
                    return found
        none.add(name)
        return ""

    for name in operands:
        inherited(name)
    return own


def program_op_names():
    """``op_names`` of the chunk program the program kept, or None."""
    try:
        from repro import obs
    except ImportError:
        return None
    text = obs.program_text(PROGRAM)
    return op_names(text) if text else None


def scope_of(op_name: str, scopes, depth: int = 1):
    """The scope path (``depth`` components from the scope on, e.g.
    ``teacher/resnet18`` at depth 2) of an ``op_name``, or None."""
    parts = [m.group(1) for m in map(_WRAPPED.fullmatch,
                                     op_name.split(";")[0].split("/"))
             if m and not m.group(0).startswith(("jit(", "pjit("))]
    for i, p in enumerate(parts):
        if p in scopes:
            return "/".join(parts[i:i + depth])
    return None


def scope_seconds(trace, depth: int = 1, names: dict | None = None):
    """Self seconds of the window's device operations per scope path
    (None: no scope), averaged over cores; ``names`` maps instructions
    to ``op_name``s (default: ``program_op_names()``). None when the
    program has no scopes, kept no program, or the window has no
    device operation."""
    scopes = program_scopes()
    if scopes is None or not trace.devices:
        return None
    names = program_op_names() if names is None else names
    if not names:
        return None
    out: dict = {}
    for name, s in trace.op_seconds().items():
        op = names.get(name.split(" = ", 1)[0], "")
        key = scope_of(op, scopes, depth)
        out[key] = out.get(key, 0.0) + s
    return out


def ms_per_epoch(run, scope: str):
    """Device milliseconds of ``scope`` per stage-2 epoch of a traced
    window, or None."""
    if run.traffic["driver"] != "stage2" or not run.window_units:
        return None
    secs = scope_seconds(run.trace)
    if secs is None:
        return None
    return 1000.0 * secs.get(scope, 0.0) / run.window_units
