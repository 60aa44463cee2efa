"""Map the instructions of an optimized HLO module to the ``ess.*`` stage
scopes of the serve round.

The round programs wrap each stage in a ``jax.named_scope`` called
``ess.<stage>`` (:data:`repro.analysis.contracts.DEVICE_SCOPES`); XLA keeps
the scope in each instruction's ``metadata={op_name=...}``.  An
instruction belongs to the innermost ``ess.*`` component of its
``op_name``.  Two cases need more than that:

* a fusion carries the metadata of its root instruction;
* XLA's own rewrites (a dot with a size-1 batch dimension dropped, a
  cumulative sum rewritten as a reduce-window, ...) make instructions
  with no metadata at all, and the inlining of a nested jit call gives
  the ops it makes the call's own ``op_name`` (one that ends in
  ``jit(<fn>)``, naming no primitive).  Such an instruction takes the
  scope of its fused root, else of the first user that has one, else of
  the first operand that has one.

An instruction whose ``op_name`` names no ``ess.*`` scope stays
:data:`UNSCOPED`: that is a stage the program left unnamed.
"""

from __future__ import annotations

import re

PREFIX = "ess."
UNSCOPED = "unscoped"

_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%(\S+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%([^\s=]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")


def innermost_scope(op_name: str | None) -> str | None:
    """The innermost ``ess.*`` component of an ``op_name`` path;
    ``UNSCOPED`` if it names none; ``None`` without an ``op_name`` or
    where it names a call site (ends in ``jit(<fn>)``)."""
    if op_name is None or op_name.endswith(")"):
        return None
    for part in reversed(op_name.split("/")):
        if part.startswith(PREFIX):
            return part
    return UNSCOPED


def _operand_list(body: str, start: int) -> str:
    """The text of the operand list that opens just before ``start``."""
    depth = 1
    for i in range(start, len(body)):
        if body[i] == "(":
            depth += 1
        elif body[i] == ")":
            depth -= 1
            if depth == 0:
                return body[start:i]
    return body[start:]


def parse(hlo_text: str) -> tuple[str, dict[str, dict]]:
    """``(module name, {instruction: record})``; a record holds the
    instruction's ``computation``, ``opcode``, ``operands``, fused
    computation (``calls``), whether it is the computation's ``root``,
    and its ``op_name`` (``None`` without metadata)."""
    module, comp, instrs = "", "", {}
    for line in hlo_text.splitlines():
        if not module:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
            continue
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        rest = m.group(3)
        body = rest.split(", metadata=", 1)[0]
        op = _OPCODE.search(body)
        opcode = op.group(1) if op else ""
        calls = _CALLS.search(body)
        nm = _OP_NAME.search(rest)
        instrs[m.group(2)] = {
            "computation": comp, "opcode": opcode,
            "operands": _REF.findall(_operand_list(body, op.end()))
            if op else [],
            "calls": calls.group(1) if calls else None,
            "root": bool(m.group(1)),
            "op_name": nm.group(1) if nm else None}
    return module, instrs


def op_scopes(hlo_text: str) -> tuple[str, dict[str, str]]:
    """``(module name, {instruction name: scope})`` for every instruction
    of one HLO module's text (see the module docstring for the rules)."""
    module, instrs = parse(hlo_text)
    scope = {n: innermost_scope(r["op_name"]) for n, r in instrs.items()}
    roots = {r["computation"]: n for n, r in instrs.items() if r["root"]}
    users: dict[str, list[str]] = {}
    for n, r in instrs.items():
        for o in r["operands"]:
            users.setdefault(o, []).append(n)
    changed = True
    while changed:
        changed = False
        for n, r in instrs.items():
            if scope[n] is not None:
                continue
            cands = []
            if r["calls"] in roots:
                cands.append(roots[r["calls"]])
            cands += users.get(n, []) + r["operands"]
            got = next((scope[c] for c in cands
                        if scope.get(c) not in (None, UNSCOPED)), None)
            if got is not None:
                scope[n] = got
                changed = True
    return module, {n: s or UNSCOPED for n, s in scope.items()}
