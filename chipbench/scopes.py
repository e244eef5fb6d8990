"""Device time of a traced program by the named scopes its ops carry.

The program labels the parts of its decode step with ``jax.named_scope``;
a configuration file lists the names its program uses (``"scopes"`` in
``chipbench/configs/<config>.json``), and every function here takes that
list.  The text of the compiled module gives each instruction's
scope path in ``metadata={op_name="..."}``, and the trace names each op it
ran after its instruction (``%fusion.448``).  :func:`op_scopes` maps every
instruction to the innermost listed scope on its path (``layers`` is the
decode's layer loop, around the other scopes: what the loop itself moves).
An instruction the compiler made without a path (a layout copy, a fusion
of expanded scatters) takes the scope of the instructions inside its fused
computation, else that of the instructions of its own computation that use
its result, else of those it reads; what none of these names, or what the
program ran outside every scope, is :data:`UNSCOPED`.  :func:`scope_s`
sums the self time of a module's ops in a :class:`chipbench.trace.Trace`'s
window by scope, and :func:`scope_ms` gives it per execution.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, List, Optional, Sequence, Tuple

UNSCOPED = "(unscoped)"
# ops that only route values (their scope says nothing of the work)
ROUTING = {"parameter", "constant", "tuple", "get-tuple-element", "while",
           "conditional", "call"}

_HEAD = re.compile(r"(?:ENTRY )?%([\w.\-]+) ")
_INST = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = .*? ([a-z][\w\-]*)\((.*)$")


def scope_of(op_name: str, names: Sequence[str]) -> str:
    """The innermost scope of ``names`` on an ``op_name`` path; the path's
    last part names the primitive (``gather`` is one), so it is left out."""
    for part in reversed(op_name.split("/")[:-1]):
        if part in names:
            return part
    return UNSCOPED


def _computations(hlo_text: str,
                  names: Sequence[str]) -> Dict[str, List[dict]]:
    comps: Dict[str, List[dict]] = {}
    for block in re.split(r"\n(?=\S)", hlo_text):
        head = _HEAD.match(block)
        if not head:
            continue
        insts = []
        for line in block.split("\n")[1:]:
            m = _INST.match(line)
            if not m:
                continue
            name, opcode, rest = m.groups()
            path = re.search(r'op_name="([^"]*)"', rest)
            insts.append({
                "name": name, "opcode": opcode, "path": bool(path),
                "root": line.lstrip().startswith("ROOT"),
                "scope": (scope_of(path.group(1), names) if path
                          else UNSCOPED),
                "calls": re.findall(r"calls=%([\w.\-]+)", rest),
                "operands": re.findall(r"%([\w.\-]+)",
                                       rest.split("), ")[0])})
        comps[head.group(1)] = insts
    return comps


def op_scopes(hlo_text: str, names: Sequence[str]) -> Dict[str, str]:
    """``{"%instruction": scope}`` for every instruction of a compiled
    module's text (``Compiled.as_text()``), over the scopes ``names``."""
    comps = _computations(hlo_text, names)

    def inner(comp: str) -> str:
        insts = comps.get(comp, [])
        for i in sorted(insts, key=lambda i: not i["root"]):
            if i["scope"] != UNSCOPED:
                return i["scope"]
        return UNSCOPED

    out: Dict[str, str] = {}
    for insts in comps.values():
        scope = {}
        for i in insts:
            s = i["scope"]
            for c in i["calls"]:
                if s == UNSCOPED and not i["path"]:
                    s = inner(c)
            scope[i["name"]] = s
        users = collections.defaultdict(list)
        for i in insts:
            for o in i["operands"]:
                users[o].append(i["name"])
        open_ = [i for i in insts if scope[i["name"]] == UNSCOPED
                 and not i["path"] and i["opcode"] not in ROUTING]

        def settle(near) -> bool:
            """Give each open instruction the commonest scope of its
            ``near`` instructions, until none changes."""
            moved, changed = False, True
            while changed:
                changed = False
                for i in open_:
                    if scope[i["name"]] != UNSCOPED:
                        continue
                    got = [scope[n] for n in near(i)
                           if scope.get(n, UNSCOPED) != UNSCOPED]
                    if got:
                        scope[i["name"]] = collections.Counter(
                            got).most_common(1)[0][0]
                        changed = moved = True
            return moved

        while (settle(lambda i: users[i["name"]])
               | settle(lambda i: i["operands"])):
            pass
        out.update({f"%{k}": v for k, v in scope.items()})
    return out


def _module_ops(trace, module: str) -> List[Tuple[str, float]]:
    """``(op, self seconds)`` of ``module``'s ops that start in the trace's
    window; self time, so a ``while`` op's body is not counted twice."""
    w0, w1 = trace.window
    return [(op, own) for (mod, op, s, _), own in zip(trace.ops, trace.self_s)
            if mod == module and w0 <= s < w1]


def scope_s(trace, module: str, scopes: Dict[str, str],
            names: Sequence[str]) -> Dict[str, float]:
    """Self seconds of ``module``'s ops in the trace's window by scope
    (each of ``names`` and :data:`UNSCOPED`)."""
    out = {s: 0.0 for s in (*names, UNSCOPED)}
    for op, own in _module_ops(trace, module):
        out[scopes.get(op, UNSCOPED)] += own
    return out


def scope_ms(trace, module: str, scopes: Dict[str, str],
             names: Sequence[str]) -> Tuple[Optional[Dict[str, float]], int]:
    """Device milliseconds per execution of ``module`` in the trace's
    window by scope, and the count of its ops there that ``scopes`` (the
    module's text) does not name.  The split is None where that count is
    not 0 (the text is of another program) or nothing executed."""
    unmapped = sum(op not in scopes for op, _ in _module_ops(trace, module))
    n = len(trace.executions(module))
    if unmapped or not n:
        return None, unmapped
    return ({k: v * 1e3 / n
             for k, v in scope_s(trace, module, scopes, names).items()},
            unmapped)
