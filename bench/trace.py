"""Reduce a JAX profiler trace (``.xplane.pb``) to per-device numbers.

Only the traced window counts: the interval of the host event named
``window`` (a ``jax.profiler.TraceAnnotation`` the harness puts around the
steps it traces). Device events are clipped to it.

Per device plane (``/device:TPU:<n>``) this gives:

- ``busy_s``: the union of the intervals of the ops on the ``XLA Ops``
  line (an async collective counts from its ``-start`` to the end of its
  ``-done``);
- ``programs``: for each XLA program (the ``XLA Modules`` line, names
  without the ``(<id>)`` suffix), the union of its ops' intervals;
- ``collective_s``: the union of the collective ops' intervals, an op
  being a collective by the opcode in its HLO text (``all-reduce``,
  ``all-gather``, ``reduce-scatter``, ``collective-permute``,
  ``all-to-all`` and their ``-start``/``-done`` forms), whatever its name
  (JAX names some after the primitive, such as ``psum.1726``);
- ``collective_ops``: the names of the ops counted as collectives;
- ``collective_exposed_s``: the part of ``collective_s`` during which no
  other op runs on that device;
- ``ops``: seconds per op, as ``<program>/<HLO name>`` (such as
  ``jit_step/fusion.4``); ``gaps``: the idle intervals.
"""

from __future__ import annotations

import dataclasses
import re

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast",
               "ragged-all-to-all")


def union(intervals):
    """Merge (start, end) intervals; returns the sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def length(merged) -> float:
    return float(sum(e - s for s, e in merged))


def minus(a, b):
    """Parts of disjoint sorted intervals ``a`` not covered by ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _clip(s, e, w0, w1):
    s, e = max(s, w0), min(e, w1)
    return (s, e) if e > s else None


@dataclasses.dataclass
class Device:
    name: str
    busy_s: float
    programs: dict
    collective_s: float
    collective_exposed_s: float
    collective_ops: set
    ops: dict
    gaps: list          # [(start_s, end_s)] idle intervals on the trace clock


@dataclasses.dataclass
class Reduced:
    window_s: float
    window: tuple       # (start_s, end_s) on the trace clock
    devices: list
    host: list          # [(name, start_s, end_s)] host events in the window

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        return sum(d.busy_s for d in self.devices) / max(len(self.devices), 1)


def _window(planes, name):
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == name:
                    return float(ev.start_ns), float(ev.end_ns)
    raise ValueError(f"no host event named {name!r} in the trace")


def _base(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name).strip()


def op_name(text: str) -> str:
    """``%fusion.4 = f32[...] fusion(...)`` -> ``fusion.4``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def opcode(text: str) -> str:
    """``%fusion.4 = f32[8]{0} fusion(%p), kind=kLoop`` -> ``fusion``; a
    tuple shape ``(f32[], f32[8])`` is skipped whole. '' where ``text``
    is not an HLO instruction."""
    if " = " not in text:
        return ""
    rhs = text.split(" = ", 1)[1].lstrip()
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                rhs = rhs[i + 1:]
                break
    else:
        rhs = rhs.split(" ", 1)[-1]
    m = re.match(r"\s*([a-z][\w-]*)\(", rhs)
    return m.group(1) if m else ""


def collective(code: str) -> str:
    """'start', 'done' or 'op' for a collective opcode, else ''."""
    for c in COLLECTIVES:
        if code == c:
            return "op"
        if code in (c + "-start", c + "-done"):
            return code.rsplit("-", 1)[1]
    return ""


_OPERAND = re.compile(r"%([\w.-]+)")


def reduce(profile, window: str = "bench.window") -> Reduced:
    """``profile``: a ``jax.profiler.ProfileData``."""
    planes = list(profile.planes)
    w0, w1 = _window(planes, window)
    host = []
    devices = []
    for plane in planes:
        if not DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                for ev in line.events:
                    c = _clip(ev.start_ns, ev.end_ns, w0, w1)
                    if c and ev.name != window:
                        host.append((ev.name, c[0] * 1e-9, c[1] * 1e-9))
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        ops = []
        for ev in lines.get(OPS_LINE, []):
            c = _clip(ev.start_ns, ev.end_ns, w0, w1)
            if c:
                ops.append((ev.name, c[0], c[1]))
        if not ops:
            continue
        modules = []
        for ev in lines.get(MODULES_LINE, []):
            c = _clip(ev.start_ns, ev.end_ns, w0, w1)
            if c:
                modules.append((_base(ev.name), c[0], c[1]))
        modules.sort(key=lambda m: m[1])
        # one item per op; an async collective spans its -start to its -done
        items, starts, coll_ops = [], {}, set()
        for text, s, e in sorted(ops, key=lambda o: o[1]):
            name = op_name(text)
            kind = collective(opcode(text))
            coll = bool(kind)
            if coll:
                coll_ops.add(name)
            if kind == "start":
                starts[name] = (name, s)
                continue
            if kind == "done":
                # its first operand (shapes hold no ``%``) is its -start
                m = _OPERAND.search(text.split(" = ", 1)[1])
                if m and m.group(1) in starts:
                    name, s = starts.pop(m.group(1))
            items.append((name, s, e, coll))
        for name, s in starts.values():      # started, not done in window
            items.append((name, s, w1, True))
        items.sort(key=lambda it: it[1])
        busy = union((s, e) for _, s, e, _ in items)
        per_prog: dict[str, list] = {}
        per_op: dict[str, float] = {}
        j = 0
        for name, s, e, _ in items:
            while j < len(modules) and modules[j][2] <= s:
                j += 1
            prog = modules[j][0] if (j < len(modules)
                                     and modules[j][1] <= s) else "?"
            per_prog.setdefault(prog, []).append((s, e))
            key = f"{prog}/{name}"
            per_op[key] = per_op.get(key, 0.0) + (e - s) * 1e-9
        coll_u = union((s, e) for _, s, e, c in items if c)
        exposed = minus(coll_u, union((s, e) for _, s, e, c in items
                                      if not c))
        gaps = [((a * 1e-9), (b * 1e-9))
                for a, b in minus([[w0, w1]], busy)]
        devices.append(Device(
            name=plane.name, busy_s=length(busy) * 1e-9,
            programs={k: length(union(v)) * 1e-9
                      for k, v in per_prog.items()},
            collective_s=length(coll_u) * 1e-9,
            collective_exposed_s=length(exposed) * 1e-9,
            collective_ops=coll_ops, ops=per_op, gaps=gaps))
    return Reduced(window_s=(w1 - w0) * 1e-9, window=(w0 * 1e-9, w1 * 1e-9),
                   devices=devices, host=host)


def top_ops(red: Reduced, n: int = 10):
    """The ``n`` op names with the most device seconds, summed over the
    devices."""
    tot: dict[str, float] = {}
    for d in red.devices:
        for k, v in d.ops.items():
            tot[k] = tot.get(k, 0.0) + v
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def idle_gaps(red: Reduced, n: int = 10):
    """The ``n`` longest idle gaps over all devices, each named by the
    innermost host event (the shortest) under the gap's midpoint."""
    out = []
    for d in red.devices:
        for s, e in d.gaps:
            mid = 0.5 * (s + e)
            inner = [(he - hs, name) for name, hs, he in red.host
                     if hs <= mid <= he]
            best = min(inner)[1] if inner else "host: no event"
            out.append((f"{d.name} {best}", e - s))
    return sorted(out, key=lambda kv: -kv[1])[:n]
