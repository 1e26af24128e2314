"""Collective operations of a traced window: their device time per chip,
and the part of it during which nothing else ran on that chip.

A collective is found by its HLO opcode (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all, with their async
``-start`` and ``-done`` parts), never by an instruction's name, so a
renamed instruction is still counted. The opcode comes from the chunk
program's compiled HLO text where the program kept one (a TPU trace
cuts long op names short, and an async start's tuple type can push its
opcode past the cut), else from the op's own HLO text in the trace.

Exposed time is what the rate pays for: per chip, the union of the
collectives' intervals less the union of every other operation's, where
only innermost operations count as running (a ``while`` that encloses
the whole chunk does not hide a collective inside it).
"""
from __future__ import annotations

import re

from harness.trace import _union

OPCODES = ("all-reduce", "all-gather", "reduce-scatter",
           "collective-permute", "all-to-all")
_COLLECTIVE = re.compile(r"(?:%s)(?:-start|-done)?" % "|".join(OPCODES))
_TEXT_OPCODE = re.compile(r"\s([a-z][\w-]*)\(")
_INSTR = re.compile(r"^\s*(?:ROOT )?(%[^\s=]+) = (.*)$", re.M)


def opcode(text: str):
    """The opcode of one HLO instruction's text (``%x = type op(...)``),
    or None where the text stops before it."""
    lhs, eq, rest = text.partition(" = ")
    m = _TEXT_OPCODE.search(" " + rest) if eq else None
    return m.group(1) if m else None


def opcodes(hlo_text: str) -> dict:
    """``%instruction`` -> opcode for an HLO module's text."""
    return {name: opcode(name + " = " + rest)
            for name, rest in _INSTR.findall(hlo_text)}


def program_opcodes():
    """``opcodes`` of the chunk program the program kept, or None."""
    from harness import scopes
    try:
        from repro import obs
    except ImportError:
        return None
    text = obs.program_text(scopes.PROGRAM)
    return opcodes(text) if text else None


def is_collective(name: str, codes: dict | None = None) -> bool:
    """Whether the device op named ``name`` (its HLO text) is a
    collective; ``codes`` maps instructions to opcodes."""
    code = (codes or {}).get(name.split(" = ", 1)[0]) or opcode(name)
    return bool(code and _COLLECTIVE.fullmatch(code))


def _innermost(evs):
    """The events that enclose no other event of their line."""
    evs = sorted(evs, key=lambda e: (e[1], -e[2]))
    outer, stack = set(), []
    for i, (_, s, _, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            outer.add(stack[-1])
        stack.append(i)
    return [e for i, e in enumerate(evs) if i not in outer]


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _less(a, b) -> int:
    """Length of the union ``a`` less the union ``b`` (both merged)."""
    out, j = 0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out += e - cur
    return out


def collective_seconds(trace, codes: dict | None = None):
    """(seconds with a collective on the chip, seconds of those with no
    other operation running), each the mean over the chips of the
    window, or None for a window with no device operation."""
    if not trace.devices:
        return None
    t0, t1 = trace.t0, trace.t1
    total = exposed = 0
    for evs in trace.devices.values():
        coll, other = [], []
        for e in evs:
            (coll if is_collective(e[0], codes) else other).append(e)
        clip = [(max(s, t0), min(s + d, t1)) for _, s, d, _ in coll]
        busy = _union([(max(s, t0), min(s + d, t1))
                       for _, s, d, _ in _innermost(other)])
        coll = _union([iv for iv in clip if iv[1] > iv[0]])
        total += _length(coll)
        exposed += _less(coll, busy)
    n = len(trace.devices)
    return total / n / 1e9, exposed / n / 1e9


def ms_per_epoch(run, exposed: bool):
    """Collective device milliseconds per stage-2 epoch of a traced
    window (``exposed``: only the part no other op overlaps), or None."""
    if run.traffic["driver"] != "stage2" or not run.window_units \
            or not run.trace.devices:
        return None
    secs = collective_seconds(run.trace, program_opcodes())
    return 1000.0 * secs[exposed] / run.window_units
