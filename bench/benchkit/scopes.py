"""Device time by the program's named scopes.

The program names the work of its kernels and of the paged cache with
``jax.named_scope``: ``qmm[<mode>]``, ``qconv[<mode>]`` and
``kv_page_view``.  The names reach the compiled program as each
instruction's ``op_name`` metadata, and the profiler's op events carry
the instruction's name: a TPU names each op by its HLO text
(``%fusion.35 = bf16[...] fusion(...)``) and the CPU backend gives
``hlo_op``/``hlo_module`` stats.  So an op's scope is found by joining
``(module, instruction)`` to the compiled text of the module
(``jax.stages.Compiled.as_text()``).

An instruction's scope is the innermost named scope in its own
``op_name``; one the compiler made without an ``op_name`` (a fusion
wrapper, a copy, a layout change) takes the scope of its called
computation's root, else of its first operand that has one.  A loop, a
conditional or a call spans the ops of its body and is left out.

The compile cache may hand the device a program compiled from another
version of the code, with that version's scopes; ``programs.py`` gives
each module's text twice (as run, and with this code's metadata) and
``module_scopes`` pairs them.  A recorded trace may carry each op's
scope as a ``scope`` stat; then no compiled text is needed.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import re
from typing import (Callable, Dict, Iterable, List, NamedTuple, Optional,
                    Tuple)

from . import trace

SCOPE = re.compile(r"^(kv_page_view|qmm\[\w+\]|qconv\[\w+\])$")
CONTROL_FLOW = ("while", "conditional", "call")

_COMP = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")


def scope_in(op_name: str) -> Optional[str]:
    """The innermost named scope in an ``op_name`` path, or None."""
    for part in reversed(op_name.split("/")):
        if SCOPE.match(part):
            return part
    return None


def split_instruction(rhs: str) -> Tuple[str, str, str]:
    """``<type> <opcode>(<operands>)<attributes>`` -> (type, opcode,
    the rest after the opcode's ``(``); a tuple type nests brackets."""
    depth, i = 0, len(rhs)
    for i, ch in enumerate(rhs):
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == " " and depth == 0:
            break
    opcode, _, rest = rhs[i + 1:].partition("(")
    return rhs[:i], opcode, rest


@dataclasses.dataclass
class Instruction:
    opcode: str
    type: str                       # result type with its layout
    scope: Optional[str]


def module_scopes(text: str, own: Optional[str] = None
                  ) -> Tuple[str, Dict[str, Instruction]]:
    """(module name, instruction name -> Instruction) of a compiled
    module's text.

    ``own``: the same program compiled from this code, where ``text``
    (the program as the device ran it, which the compile cache may have
    kept from another version of the code) can carry other scopes and,
    since instruction names follow the ``op_name``s, other names.  The
    two are matched instruction by instruction, in order: ``text`` gives
    the names, ``own`` the scopes.  Programs that differ in more than
    names give an empty table.
    """
    name, order, table = _parse(text)
    if own is None:
        return name, table
    _, own_order, own_table = _parse(own)
    pairs = list(zip(order, own_order))
    if len(order) != len(own_order) or any(
            (table[a].opcode, table[a].type)
            != (own_table[b].opcode, own_table[b].type) for a, b in pairs):
        return name, {}
    return name, {a: own_table[b] for a, b in pairs}


def _parse(text: str):
    """(module name, instruction names in text order, name ->
    Instruction)."""
    name = text.split("\n", 1)[0].split(" ")[1].rstrip(",")
    raw: Dict[str, dict] = {}
    roots: Dict[str, str] = {}
    comp = None
    for line in text.split("\n"):
        m = _COMP.match(line) if " = " not in line else None
        if m:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m or comp is None:
            continue
        iname, rhs = m.groups()
        typ, opcode, rest = split_instruction(rhs)
        meta = _OP_NAME.search(rest)
        raw[iname] = {"opcode": opcode, "type": typ,
                      "op_name": meta.group(1) if meta else None,
                      "calls": _CALLS.findall(rest),
                      "operands": _OPERAND.findall(rest.split(")", 1)[0])}
        if line.lstrip().startswith("ROOT "):
            roots[comp] = iname

    memo: Dict[str, Optional[str]] = {}

    def scope(iname: str, seen=()) -> Optional[str]:
        if iname in memo:
            return memo[iname]
        r = raw.get(iname)
        if r is None or iname in seen:
            return None
        seen = seen + (iname,)
        if r["op_name"] is not None:
            out = scope_in(r["op_name"])
        else:
            out = None
            for c in r["calls"]:
                if c in roots:
                    out = scope(roots[c], seen)
                    if out:
                        break
            if out is None:
                for o in r["operands"]:
                    out = scope(o, seen)
                    if out:
                        break
        memo[iname] = out
        return out

    return name, list(raw), {k: Instruction(r["opcode"], r["type"], scope(k))
                             for k, r in raw.items()}


class Op(NamedTuple):
    """One device op of the window, attributed."""
    module: str
    execution: object               # the module execution it ran in
    scope: Optional[str]
    seconds: float                  # clipped to the window
    event: dict


def module_base(name: str) -> str:
    """``jit_serve_step(1456...)`` -> ``jit_serve_step``."""
    return name.split("(", 1)[0]


class ScopedOps:
    """The device ops of a traced window, each with the module execution
    it ran in and its named scope.

    ``texts`` gives the compiled text of the modules to attribute, each
    a text or a pair for ``module_scopes`` (a callable is called once,
    and only if some op carries no ``scope`` stat).  An op the compiled text lacks, or whose HLO text names
    another result type than the compiled text does (another program
    than the one compiled), counts in ``mismatched``, and such a module
    is not attributed at all.
    """

    def __init__(self, summary, texts: Callable[[], Iterable[str]]
                 | Iterable[str] = ()):
        self.summary = summary
        self.mismatched: Dict[str, int] = collections.Counter()
        # every execution on the ops' planes: one that began a little
        # before the window (the host and device clocks are aligned to
        # a fraction of a millisecond) still holds ops inside it
        mods = sorted((e for e in summary.events
                       if e["line"] in trace.MODULE_LINES
                       and trace.is_device_plane(e["plane"])),
                      key=lambda e: e["start_ns"])
        starts = [e["start_ns"] for e in mods]
        tables: Optional[Dict[str, Dict[str, Instruction]]] = None
        rows = []
        for e in summary.ops:
            st = e["stats"]
            if "hlo_module" in st:              # CPU: stats name both
                module, execution = st["hlo_module"], st.get("run_id")
                iname = st.get("hlo_op", e["name"])
            else:                               # TPU: the enclosing module
                j = bisect.bisect_right(starts, e["start_ns"]) - 1
                while j >= 0 and mods[j]["plane"] != e["plane"]:
                    j -= 1
                if j < 0 or e["start_ns"] > (mods[j]["start_ns"]
                                             + mods[j]["dur_ns"]):
                    continue
                module = module_base(mods[j]["name"])
                execution = j
                iname = e["name"].split(" = ", 1)[0].lstrip("%")
            if "scope" in st:
                scope, opcode = st["scope"] or None, _opcode(e["name"])
            else:
                if tables is None:
                    tables = {}
                    for t in (texts() if callable(texts) else texts):
                        mname, table = module_scopes(
                            *(t if isinstance(t, tuple) else (t,)))
                        tables[mname] = table
                table = tables.get(module)
                ins = None if table is None else table.get(iname)
                if ins is None:
                    scope, opcode = None, _opcode(e["name"])
                    if table is not None and (" = " in e["name"]
                                              or "hlo_op" in st):
                        self.mismatched[module] += 1    # not in the text
                else:
                    scope, opcode = ins.scope, ins.opcode
                    if " = " in e["name"]:
                        typ = split_instruction(
                            e["name"].split(" = ", 1)[1])[0]
                        if typ != ins.type:
                            self.mismatched[module] += 1
            if opcode in CONTROL_FLOW:
                continue
            a = max(e["start_ns"], summary.t0)
            b = min(e["start_ns"] + e["dur_ns"], summary.t1)
            rows.append(Op(module, execution, scope, max(b - a, 0.0) / 1e9,
                           e))
        bad = set(self.mismatched)
        self.rows: List[Op] = [r for r in rows if r.module not in bad]

    def executions(self, fragment: str) -> int:
        """Executions of the modules whose name holds ``fragment`` that
        ran ops in the window."""
        return len({(r.module, r.execution) for r in self.rows
                    if fragment in r.module})

    def seconds_by_scope(self, fragment: str = "") -> Dict[str, float]:
        """Device seconds in the window by scope (None: no scope) in the
        modules whose name holds ``fragment``."""
        out: Dict[Optional[str], float] = collections.Counter()
        for r in self.rows:
            if fragment in r.module:
                out[r.scope] += r.seconds
        return dict(out)

    def seconds(self, fragment: str, match: Callable[[str], bool]) -> float:
        return sum(v for s, v in self.seconds_by_scope(fragment).items()
                   if s is not None and match(s))

    def any_scope(self, match: Callable[[str], bool]) -> bool:
        return any(r.scope is not None and match(r.scope) for r in self.rows)


def _opcode(name: str) -> str:
    if " = " not in name:
        return ""
    return split_instruction(name.split(" = ", 1)[1])[1]
