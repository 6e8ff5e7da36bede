"""Which device operations of a compiled program belong to one
``jax.named_scope``, read from the program's optimized HLO text
(``compiled.as_text()``).

A profiler trace names each device operation by its HLO instruction and
carries no scope. So the operations are taken from the executed
computations of the compiled module (the entry, the bodies and
conditions of its loops, the branches of its conditionals and the bodies
of its calls; a fusion's computation runs as the one fusion operation),
each rendered as ``chipbench.trace.op_name`` renders a trace event. A
rendered name belongs to the scope when every instruction rendered so
has the scope in its ``op_name`` metadata; a name shared with any
instruction outside the scope, or with none, is left out.
"""
from __future__ import annotations

import re

from chipbench.trace import op_name

_HEADER = re.compile(r"^(?:ENTRY )?%([^\s(]+) .*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT )?(%\S+ = .*)$")
_CALLED = re.compile(r"\b(?:body|condition|true_computation|false_computation)"
                     r"=%([^\s,)}]+)")
_CALLS = re.compile(r"\b(?:calls|to_apply)=%([^\s,)}]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_META = re.compile(r'\bop_name="([^"]*)"')


def computations(text: str) -> tuple[str, dict]:
    """(entry name, {computation name: its instruction texts})."""
    comps: dict[str, list[str]] = {}
    entry, cur = None, None
    for line in text.splitlines():
        h = _HEADER.match(line)
        if h:
            cur = comps.setdefault(h.group(1), [])
            if line.startswith("ENTRY"):
                entry = h.group(1)
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            cur.append(m.group(1))
    if entry is None:
        raise ValueError("no ENTRY computation in the HLO text")
    return entry, comps


def executed(text: str) -> list[str]:
    """Instruction texts of every computation that runs as operations of
    its own: the entry and what it reaches through loops, conditionals
    and calls (fusion computations excluded)."""
    entry, comps = computations(text)
    seen, todo, out = {entry}, [entry], []
    while todo:
        for ins in comps.get(todo.pop(), []):
            out.append(ins)
            names = _CALLED.findall(ins)
            if re.search(r"\scall\(", ins):
                names += _CALLS.findall(ins)
            for b in _BRANCHES.findall(ins):
                names += [n.strip().lstrip("%") for n in b.split(",")]
            for n in names:
                if n in comps and n not in seen:
                    seen.add(n)
                    todo.append(n)
    return out


def scope_op_names(text: str, scope: str) -> tuple[list[str], dict]:
    """Rendered op names that lie wholly under ``scope``, and counts:
    the scope's executed instructions and how many of them fall under
    those names."""
    by_name: dict[str, list[bool]] = {}
    for ins in executed(text):
        meta = _META.search(ins)
        inside = bool(meta) and any(scope in part
                                    for part in meta.group(1).split("/"))
        by_name.setdefault(op_name(ins), []).append(inside)
    names = sorted(n for n, flags in by_name.items() if all(flags))
    in_scope = sum(sum(flags) for flags in by_name.values())
    kept = sum(len(by_name[n]) for n in names)
    return names, {"scope_instructions": in_scope,
                   "unambiguous_instructions": kept,
                   "op_names": len(names)}
