"""Reduce a JAX profiler trace (``.xplane.pb``) of one window to numbers.

* the window: the host span named by the harness around the measured loop;
* busy time: the union of the intervals in which an XLA operation ran on
  each device, inside the window, averaged over the devices;
* device time and calls per XLA program (``XLA Modules``), and per leaf
  operation (``XLA Ops``, an operation that encloses others, such as a
  loop, is left out so that no time counts twice);
* host spans inside the window, counted by name;
* idle gaps between busy intervals, each named by what the host was doing
  over most of it: the innermost benchmark span (``bench.*``) and the
  innermost other event of the thread that ran the window.

Device and host clocks in a trace can disagree by a millisecond or two, so
a gap is named by the host events covering most of it, not by its ends.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

_SUFFIX = re.compile(r"\(\d+\)$")
_LAYOUT = re.compile(r"\{[^}]*\}")


def module_base(name: str) -> str:
    """``jit_decode_step(1234)`` -> ``jit_decode_step``."""
    return _SUFFIX.sub("", name)


def op_label(module: str, hlo: str) -> str:
    """A short label for an HLO operation event: program, instruction name
    without its number, and output shape without layout."""
    head = hlo.split(" = ", 1)
    inst = re.sub(r"\.\d+$", "", head[0].lstrip("%"))
    shape = ""
    if len(head) > 1:
        shape = _LAYOUT.sub("", head[1].split(" ", 1)[0])
    return f"{module_base(module)}:{inst} {shape}".strip()[:120]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


@dataclass
class Summary:
    window_s: float
    busy_s: float
    modules: Dict[str, dict] = field(default_factory=dict)   # name -> count, seconds
    ops: Dict[str, float] = field(default_factory=dict)      # label -> seconds
    spans: Dict[str, int] = field(default_factory=dict)      # host span -> count
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    def idle_pct(self) -> float:
        """Share of the window in which no operation ran on the device."""
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:n]]}

    def program(self, hint: str,
                dispatches: Optional[int] = None) -> Optional[dict]:
        """Device time and calls of one program, found by name (``hint`` in
        the program's name) or, for a program the trace names only
        ``jit__unknown``, as the heaviest program with as many calls in
        the window as its host dispatches (``dispatches``)."""
        named = {k: v for k, v in self.modules.items() if hint in k}
        if named:
            return _total(named)
        if not dispatches:
            return None
        near = {k: v for k, v in self.modules.items()
                if abs(v["count"] - dispatches) <= max(1, dispatches // 50)}
        if not near:
            return None
        best = max(near, key=lambda k: near[k]["seconds"])
        return dict(self.modules[best], name=best)

    def others_like(self, name: str) -> Optional[dict]:
        """All programs with ``name``'s base name except ``name`` itself."""
        base = module_base(name)
        rest = {k: v for k, v in self.modules.items()
                if k != name and module_base(k) == base}
        return _total(rest) if rest else None


def _total(group: Dict[str, dict]) -> dict:
    return {"count": sum(v["count"] for v in group.values()),
            "seconds": sum(v["seconds"] for v in group.values())}


def _device_planes(planes, n_devices: int):
    out = [p for p in planes if re.fullmatch(r"/device:(TPU|GPU):\d+", p.name)]
    out.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    return out[:n_devices]


def _leaf_ops(events):
    """Events of one op line that enclose no other event of the line."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    out = []
    for i, (s, e, name) in enumerate(events):
        nxt = events[i + 1][0] if i + 1 < len(events) else float("inf")
        if nxt >= e or events[i + 1][1] > e:
            out.append((s, e, name))
    return out


def reduce(trace_dir: Path, window_span: str, n_devices: int = 1) -> Summary:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    return reduce_planes(list(pd.planes), window_span, n_devices)


def reduce_planes(planes, window_span: str, n_devices: int = 1) -> Summary:
    """Planes with ``name`` and ``lines``; lines with ``name`` and
    ``events``; events with ``name``, ``start_ns`` and ``duration_ns``."""
    host = [p for p in planes if p.name == "/host:CPU"]
    win, win_line = None, None
    for p in host:
        for ln in p.lines:
            for ev in ln.events:
                if ev.name == window_span:
                    win, win_line = (ev.start_ns, ev.start_ns + ev.duration_ns), ln.name
    if win is None:
        raise ValueError(f"no {window_span!r} span in the trace")
    lo, hi = win

    spans: Dict[str, int] = {}
    bench_ev, thread_ev = [], []
    for p in host:
        for ln in p.lines:
            for ev in ln.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= lo or s >= hi:
                    continue
                name = ev.name
                if name.startswith("bench."):
                    spans[name] = spans.get(name, 0) + 1
                    if name != window_span:
                        bench_ev.append((s, e, name))
                elif ln.name == win_line:
                    thread_ev.append((s, e, name))

    modules: Dict[str, dict] = {}
    ops: Dict[str, float] = {}
    busy_total = 0.0
    busy_all: List[Tuple[float, float]] = []
    devices = _device_planes(planes, n_devices)
    for p in devices:
        lines = {ln.name: ln for ln in p.lines}
        mod_ev = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                  for ev in lines["XLA Modules"].events] \
            if "XLA Modules" in lines else []
        for s, e, name in mod_ev:
            if lo <= s < hi:
                m = modules.setdefault(name, {"count": 0, "seconds": 0.0})
                m["count"] += 1
                m["seconds"] += (min(e, hi) - s) * 1e-9
        op_ev = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                 for ev in lines["XLA Ops"].events] if "XLA Ops" in lines \
            else mod_ev
        busy = union(clip([(s, e) for s, e, _ in op_ev], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        busy_all.extend(busy)
        mods = sorted(mod_ev)
        j = 0
        for s, e, name in _leaf_ops(op_ev):
            if not (lo <= s < hi):
                continue
            while j + 1 < len(mods) and mods[j + 1][0] <= s:
                j += 1
            owner = mods[j][2] if mods and mods[j][0] <= s < mods[j][1] else "?"
            label = op_label(owner, name)
            ops[label] = ops.get(label, 0.0) + (min(e, hi) - s) * 1e-9

    busy_u = union(busy_all)
    edges = [lo] + [x for iv in busy_u for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:50]
    named = [(_name_gap(g, bench_ev, thread_ev), (g[1] - g[0]) * 1e-9)
             for g in gaps]
    return Summary(window_s=(hi - lo) * 1e-9,
                   busy_s=busy_total * 1e-9 / max(1, len(devices)),
                   modules=modules, ops=ops, spans=spans, gaps=named[:50])


def _innermost(gap, events) -> Optional[str]:
    """The shortest event covering more than half of ``gap``."""
    gs, ge = gap
    best = None
    for s, e, name in events:
        if min(e, ge) - max(s, gs) > (ge - gs) / 2:
            if best is None or e - s < best[0]:
                best = (e - s, name)
    return best[1] if best else None


def _name_gap(gap, bench_ev, thread_ev) -> str:
    outer = _innermost(gap, bench_ev) or "no bench span"
    inner = _innermost(gap, thread_ev)
    return f"{outer} > {inner}" if inner else outer
