"""The compiled train step's instruction -> scope table, for ``record:
compile``.

A device trace names an operation by its instruction (``fusion.22``) and
carries no scope; the optimized HLO text (``compiled.as_text()``) keeps,
on every instruction and on every member of every fused computation, the
``op_name`` of the program code it came from, with the ``jax.named_scope``
(``tffm.*``) parts the step was written under.  :func:`scope_table` reads
that text into the join a trace reader needs; :func:`record_of` is what
:meth:`CompileSentinel.record <fast_tffm_tpu.obs.resource.CompileSentinel.record>`
adds to the compile record.  Plain text parsing: no jax import.

The table's lists, each instruction in one of them at most:

- ``scopes``: scope -> the instructions that carry it themselves -- the
  last ``tffm.*`` component of the ``op_name``; for a fusion, the one
  scope of its members;
- ``moved``: scope -> what the compiler made itself and the program's
  code does not name (no metadata, or an argument's name: a layout copy,
  an asynchronous copy, the loop that moves a buffer), given the scope of
  the data it moves -- its first operand's that has one, else its users'
  where they agree on one, else, inside a loop or branch, that of the
  instruction that runs it.  Kept apart from ``scopes`` so that a reader
  can tell the time the program names from the time this rule infers;
- ``mixed``: fusion -> the scopes of its members, where they carry more
  than one (parameters, constants, bitcasts, tuples and unscoped members
  aside);
- ``containers``: the ``while`` / ``conditional`` / ``call`` instructions.

Listed are the operations a trace can show: the entry computation's, and
those of each loop body and condition, branch and called computation --
never a fusion's members.  An instruction in no list is *unnamed*.
"""

from __future__ import annotations

import re
import time
from typing import NamedTuple, Optional

__all__ = ["scope_table", "record_of"]

# A computation's header, an instruction, the op_name of its metadata,
# the program's scopes in it, an operand, and the computations an
# instruction runs.
_COMP = re.compile(r"^(ENTRY\s+)?%?([^\s(]+)")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"tffm\.[A-Za-z0-9_]+")
_OPERAND = re.compile(r"%([^\s,()]+)")
_CALLS = re.compile(
    r"\b(?:calls|body|condition|to_apply|true_computation|false_computation"
    r"|branch_computations)=(\{[^}]*\}|%?[\w.\-]+)")
# Instructions that run others as operations of their own, and those
# that run nothing.
_CONTROL = frozenset({"while", "conditional", "call"})
_SILENT = frozenset({"parameter", "constant", "bitcast",
                     "get-tuple-element", "tuple"})


class _Instr(NamedTuple):
    comp: str  # the computation that holds it
    opcode: str
    scope: Optional[str]  # the last tffm.* component of its op_name
    code: bool  # its op_name is a place in the program's code
    called: list  # the computations it runs
    operands: list


def _parse(hlo_text: str):
    """``(entry, computation -> its instructions' names, name ->
    _Instr)`` of an optimized HLO module's text."""
    comps: dict = {}
    ins: dict = {}
    entry = comp = None
    for line in hlo_text.splitlines():
        if not line.strip():
            continue
        if not line[0].isspace():
            head = line.rstrip()
            m = _COMP.match(head)
            comp = None
            if head.endswith("{") and not head.startswith("HloModule"):
                comp = m.group(2)
                comps.setdefault(comp, [])
                if m.group(1):
                    entry = comp
            continue
        m = _INSTR.match(line)
        if comp is None or not m:
            continue
        rhs = m.group(2)
        if rhs.startswith("("):  # a tuple type: skip to its close
            depth = 0
            for i, c in enumerate(rhs):
                depth += (c == "(") - (c == ")")
                if not depth:
                    break
            rest = rhs[i + 1:].lstrip()
        else:
            rest = rhs.partition(" ")[2]
        opcode, _, args = rest.partition("(")
        depth, i = 1, 0
        while depth and i < len(args):
            depth += (args[i] == "(") - (args[i] == ")")
            i += 1
        op_name = _OP_NAME.search(rhs)
        op_name = op_name.group(1) if op_name else ""
        scopes = _SCOPE.findall(op_name)
        comps[comp].append(m.group(1))
        ins[m.group(1)] = _Instr(
            comp, opcode, scopes[-1] if scopes else None, "/" in op_name,
            [c.strip(" %") for group in _CALLS.findall(args[i:])
             for c in group.strip("{}").split(",")],
            _OPERAND.findall(args[:i]))
    return entry, comps, ins


def scope_table(hlo_text: str) -> dict:
    """``{"scopes": {scope: [instruction]}, "moved": {scope:
    [instruction]}, "mixed": {fusion: [scope]}, "containers":
    [instruction]}`` of a compiled step's optimized HLO text (the
    module's docstring says what each list holds)."""
    entry, comps, ins = _parse(hlo_text)

    def members(comp: str, seen: set) -> set:
        out = set()
        for name in comps.get(comp, ()):
            rec = ins[name]
            if rec.opcode == "fusion":
                for c in rec.called:
                    if c not in seen:
                        seen.add(c)
                        out |= members(c, seen)
            elif rec.scope and rec.opcode not in _SILENT:
                out.add(rec.scope)
        return out

    own: dict = {}  # name -> set of scopes, its own

    def own_scopes(name: str) -> set:
        if name not in own:
            rec = ins[name]
            found = set()
            if rec.opcode == "fusion" and rec.called:
                found = members(rec.called[0], set())
            own[name] = found or ({rec.scope} - {None})
        return own[name]

    outer: dict = {}  # computation -> scope of what runs it
    moved: dict = {}  # name -> scope of the data it moves, or None
    users: dict = {}
    for name, rec in ins.items():
        for o in rec.operands:
            users.setdefault(o, []).append(name)

    def moves(name: str):
        """One scope for an instruction, through the operands of what
        the compiler made; iterative, operand chains are long."""
        stack = [name]
        while stack:
            top = stack[-1]
            if top in moved:
                stack.pop()
                continue
            rec = ins[top]
            found = own_scopes(top)
            if found or (rec.code and rec.opcode not in _SILENT):
                moved[top] = min(found) if len(found) == 1 else None
                stack.pop()
                continue
            todo = [o for o in rec.operands if o in ins and o not in moved]
            if todo:
                stack.extend(reversed(todo))
                continue
            theirs = set() if rec.opcode in _SILENT else set().union(
                *(own_scopes(u) for u in users.get(top, ())))
            moved[top] = next(
                (moved[o] for o in rec.operands if moved.get(o)),
                min(theirs) if len(theirs) == 1 else outer.get(rec.comp))
            stack.pop()
        return moved[name]

    table: dict = {"scopes": {}, "moved": {}, "mixed": {}, "containers": []}
    todo, seen = [entry] if entry else [], set()
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for name in comps[comp]:
            rec = ins[name]
            if rec.opcode in _CONTROL:
                table["containers"].append(name)
                for c in rec.called:
                    outer.setdefault(c, moves(name))
                todo.extend(rec.called)
                continue
            if rec.opcode in _SILENT:
                continue
            found = own_scopes(name)
            if len(found) > 1:
                table["mixed"][name] = sorted(found)
            elif found:
                table["scopes"].setdefault(min(found), []).append(name)
            else:
                scope = moves(name)
                if scope:
                    table["moved"].setdefault(scope, []).append(name)
    return table


def record_of(compiled) -> dict:
    """What a compile record carries of ``compiled`` (a
    ``jax.stages.Compiled``): its scope table and the seconds that
    ``as_text`` and the parse took (``scope_table_s``), or, where either
    fails, ``scope_table_error``: the readers of the table raise on it
    rather than read a step with no names."""
    t0 = time.perf_counter()
    try:
        out = scope_table(compiled.as_text())
    except Exception as e:  # noqa: BLE001 - reported in the record
        return {"scope_table_error": f"{type(e).__name__}: {e}"}
    out["scope_table_s"] = round(time.perf_counter() - t0, 4)
    return out
