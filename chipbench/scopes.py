"""Device time of the program's named phases, read from a reduced trace.

The program names each phase of a FedNL round and of a train step with
``jax.named_scope("<path>.<phase>")``: ``fednl.oracle``, ``fednl.uplink``,
``train.observe`` and so on. The name reaches the ``op_name`` metadata of
every instruction of the compiled HLO, fusions included, wrapped by the
transform it sits directly under where there is one
(``vmap(fednl.uplink)``). An instruction belongs to the innermost such
scope of its op name; its op events in the traced window
(``trace.Reduced.ops``: chip 0, loops and calls left out, as in the
breakdown) give the scope's device time.

``run.py`` gives the per-layer readers no HLO text. ``read_per_layer``
holds the driver whose program made the trace, beside the reduced trace
it puts in the readers' context, and ``hlo_of`` takes the driver's HLO
from there. A context that carries ``"hlo"`` itself is read first. A
program without scopes, or a context without HLO, reads nothing: every
reader then returns None.
"""

from __future__ import annotations

import collections
import re
import sys

from chipbench import trace

SCOPE = re.compile(r"^(fednl|train)\.[a-z_]+$")
_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")
_OPERAND = re.compile(r"%([\w.\-]+)")


def scope_of(op_name: str) -> str | None:
    """The innermost component of an op name that names a phase, with
    the transforms that wrap it taken off."""
    found = None
    for part in op_name.split("/"):
        while not SCOPE.match(part) and _WRAPPED.match(part):
            part = _WRAPPED.match(part).group(1)
        if SCOPE.match(part):
            found = part
    return found


def scope_instructions(hlo_texts) -> dict:
    """HLO instruction name -> phase, for every instruction of the given
    programs that belongs to one. An instruction belongs to the phase of
    its own op name. One that a compiler pass made without an op name (a
    fusion it built, a sort or copy it put in) belongs to the phase of the
    root of the computation it calls, else to the one phase that its users
    share."""
    own, calls, roots = {}, {}, {}
    members = collections.defaultdict(list)
    users = collections.defaultdict(set)
    for text in hlo_texts:
        computation = None
        for line in text.splitlines():
            if line and not line[0].isspace():
                m = trace._COMPUTATION.match(line)
                computation = (m.group(1) if m and line.rstrip().endswith("{")
                               else None)
                continue
            m = trace._INSTR.match(line)
            if not m:
                continue
            name = m.group(1)
            members[computation].append(name)
            if line.lstrip().startswith("ROOT"):
                roots[computation] = name
            op = trace._OP_NAME.search(line)
            scope = op and scope_of(op.group(1))
            if scope:
                own[name] = scope
            c = trace._CALLS.search(line)
            if c:
                calls[name] = c.group(1)
            for operand in _OPERAND.findall(line.partition(" = ")[2]):
                users[operand].add(name)

    def callee(name):
        """The phase of the root of the computation ``name`` calls."""
        if name not in calls:
            return None
        root = roots.get(calls[name])
        return own.get(root) or callee(root)

    out = dict(own)
    for name in calls:
        if name not in out:
            found = callee(name)
            if found:
                out[name] = found
    pending = [n for names in members.values() for n in names if n not in out]
    while pending:
        left = []
        for name in pending:
            found = {out[u] for u in users.get(name, ()) if u in out}
            if len(found) == 1:
                out[name] = found.pop()
            else:
                left.append(name)
        if len(left) == len(pending):
            break
        pending = left
    return out


def scope_seconds(reduced, hlo_texts) -> tuple:
    """({phase: seconds}, seconds of every op) on chip 0 in the window."""
    instr = scope_instructions(hlo_texts)
    seconds = collections.Counter()
    for key, s in reduced.ops.items():
        scope = instr.get(key.partition(" ")[0])
        if scope:
            seconds[scope] += s
    return dict(seconds), sum(reduced.ops.values())


def hlo_of(ctx) -> list:
    """The compiled HLO texts of the program the context's trace ran."""
    if "hlo" in ctx:
        return ctx["hlo"]
    frame = sys._getframe(1)
    while frame is not None:
        local = frame.f_locals
        driver = local.get("driver")
        if local.get("reduced") is ctx["trace"] and hasattr(driver,
                                                             "kernel_tags"):
            return driver.kernel_tags().get("hlo", [])
        frame = frame.f_back
    return []


def scope_ms(ctx, scope: str, per: str) -> float | None:
    """Device ms under ``scope`` per FedNL round (``per="round"``) or per
    train step (``per="step"``) in the traced window."""
    units = {"round": ctx["units"], "step": ctx["calls"]}[per]
    seconds, _ = scope_seconds(ctx["trace"], hlo_of(ctx))
    if scope not in seconds or not units:
        return None
    return 1e3 * seconds[scope] / units


def scoped_share(ctx) -> float | None:
    """The share of the window's op time that lies under some phase."""
    seconds, total = scope_seconds(ctx["trace"], hlo_of(ctx))
    if not seconds or total <= 0:
        return None
    return 100.0 * sum(seconds.values()) / total
