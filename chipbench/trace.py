"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: the traced window, the device's busy intervals, the time of
each kernel of the program, collectives, and the host's spans, on one
clock.

Device planes are ``/device:TPU:<i>``; their op events sit on the line
"XLA Ops" and are named after the HLO instruction that ran: on a TPU
v5e the event's name is the instruction's whole text
(``%fusion.12 = f32[8]{0} fusion(%a), kind=kLoop, ...``), elsewhere its
bare name; both reduce to the instruction's name. A kernel is found
through the compiled program's HLO text: a ``tpu_custom_call`` whose op
name metadata holds ``jit(<wrapper>)`` belongs to the kernel whose
jitted wrapper that is (``kernel_tags()['scopes']`` of the driver), and
so does a fusion that calls the computation holding such a call. The
window is the host span ``bench.window`` that ``run.py`` opens around
the measured calls.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute|send|recv", re.I)
CONTAINER = re.compile(r"(while|conditional|call)\b")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


def instruction(event_name: str) -> tuple:
    """(name, opcode) of the HLO instruction an op event ran, from the
    event's name: the instruction's whole text or its bare name (whose
    opcode is then unknown, "")."""
    name, eq, rest = event_name.lstrip("%").partition(" = ")
    if not eq:
        return event_name.strip(), ""
    if rest.startswith("("):  # a tuple shape: skip to its closing paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    return name.strip(), rest.strip().partition("(")[0]


def is_collective(name: str, opcode: str) -> bool:
    return bool(COLLECTIVE.match(opcode) or COLLECTIVE.match(name))


def _is_container(name: str, opcode: str) -> bool:
    """A loop, branch or call: its event spans the ops it runs, which have
    events of their own. It counts as busy, not as an op of its own."""
    return bool(CONTAINER.match(opcode or name))


def kernel_instructions(hlo_texts, scopes: dict) -> dict:
    """HLO instruction name -> kernel tag, for every custom call of the
    given programs whose op name lies under a kernel's jitted wrapper,
    and for every fusion that calls a computation holding one."""
    out, fused = {}, {}
    for text in hlo_texts:
        computation, calls = None, []
        for line in text.splitlines():
            if line and not line[0].isspace():
                m = _COMPUTATION.match(line)
                computation = m.group(1) if m and line.rstrip().endswith("{") else None
                continue
            m = _INSTR.match(line)
            if not m:
                continue
            c = _CALLS.search(line)
            if c:
                calls.append((m.group(1), c.group(1)))
            op = _OP_NAME.search(line)
            if 'custom_call_target="tpu_custom_call"' not in line or not op:
                continue
            for tag, scope in scopes.items():
                if f"jit({scope})" in op.group(1):
                    out[m.group(1)] = tag
                    if computation is not None:
                        fused[computation] = tag
        for caller, callee in calls:
            if callee in fused:
                out[caller] = fused[callee]
    return out


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _overlap(merged, s, e) -> int:
    return sum(max(0, min(e, b) - max(s, a)) for a, b in merged)


@dataclass
class Reduced:
    window: tuple                       # (start_ns, end_ns) of bench.window
    busy: dict                          # chip -> merged op intervals in window
    kernels: dict                       # tag -> {"seconds", "events"} mean per chip
    ops: collections.Counter            # "name opcode" -> seconds, chip 0
    collective_exposed: dict            # chip -> seconds a collective ran alone
    host_spans: list = field(default_factory=list)  # (start, end, name)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran on the device, mean over chips."""
        per = [sum(b - a for a, b in iv) for iv in self.busy.values()]
        return sum(per) / len(per) * 1e-9 if per else 0.0

    def idle_gaps(self, chip: int = 0):
        """Idle intervals of one chip inside the window, longest first,
        each named by the innermost host span that covers its middle."""
        iv = self.busy.get(chip, [])
        edges = [self.window[0]] + [x for ab in iv for x in ab] + [self.window[1]]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        named = []
        for s, e in gaps:
            mid = (s + e) // 2
            cover = [(hs, he, n) for hs, he, n in self.host_spans
                     if hs <= mid <= he and n != WINDOW_SPAN]
            name = min(cover, key=lambda c: c[1] - c[0])[2] if cover else "host: none"
            named.append((name, (e - s) * 1e-9))
        return sorted(named, key=lambda x: -x[1])

    def breakdown(self) -> dict:
        return {
            "device_ops": [[n, s] for n, s in self.ops.most_common(10)],
            "idle_gaps": [[n, s] for n, s in self.idle_gaps()[:10]],
        }


def _xplane_file(tdir: str) -> str:
    files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {tdir}")
    return max(files, key=os.path.getmtime)


def reduce_dir(tdir: str, tags: dict, chips: int) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(_xplane_file(tdir)), tags,
                          chips)


def reduce_profile(pdata, tags: dict, chips: int) -> Reduced:
    """``tags``: {"scopes": {tag: jitted wrapper}, "hlo": [hlo texts]}."""
    instr = kernel_instructions(tags.get("hlo", []), tags.get("scopes", {}))
    host_spans, window = [], None
    device_events = {}
    for plane in pdata.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            if chip < chips:
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        device_events[chip] = [
                            (e.start_ns, e.end_ns, *instruction(e.name))
                            for e in line.events]
            continue
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host_spans.append((e.start_ns, e.end_ns, e.name))
                        if e.name == WINDOW_SPAN:
                            window = (e.start_ns, e.end_ns)
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    if not device_events:
        raise ValueError("the trace holds no device op events")
    w0, w1 = window
    busy, kernels, exposed = {}, collections.defaultdict(
        lambda: {"seconds": 0.0, "events": 0}), {}
    ops = collections.Counter()
    for chip, evs in device_events.items():
        evs = [(max(s, w0), min(e, w1), n, op) for s, e, n, op in evs
               if e > w0 and s < w1]
        busy[chip] = _union((s, e) for s, e, _, _ in evs)
        compute = _union((s, e) for s, e, n, op in evs
                         if not is_collective(n, op))
        coll = _union((s, e) for s, e, n, op in evs if is_collective(n, op))
        exposed[chip] = sum((e - s) - _overlap(compute, s, e)
                            for s, e in coll) * 1e-9
        for s, e, n, op in evs:
            tag = instr.get(n)
            if tag is not None:
                kernels[tag]["seconds"] += (e - s) * 1e-9 / len(device_events)
                kernels[tag]["events"] += 1
            if chip == min(device_events) and not _is_container(n, op):
                ops[f"{n} {op}".strip()] += (e - s) * 1e-9
    return Reduced(window=window, busy=busy, kernels=dict(kernels), ops=ops,
                   collective_exposed=exposed, host_spans=host_spans)
