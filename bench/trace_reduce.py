"""Reduce a profiler trace to device busy time, per-scope device time and
idle gaps.

Device planes are `/device:TPU:<n>`; their "XLA Ops" line holds one event
per executed HLO op, nested (a `while` op spans its body's ops). Each op
carries its `tf_op` name stack, where `jax.named_scope` scopes appear, e.g.
`jit(step)/vmap(mgmt_round)/while/body/...`. The reduction:

- busy: the union of the op intervals inside the traced window;
- self time of an op: its duration less the union of the ops nested in it;
- effective scope: the op's own name stack, or, for a container op with
  none (a `while`), the effective scope of its longest nested op;
- time in scope S: the summed self time of ops whose effective scope names
  S as a path component (so nested time is never counted twice);
- idle gaps: the stretches of the window with no op running, each
  attributed to the innermost host annotation (`bench_*`) open at its
  midpoint.
"""
from __future__ import annotations

import bisect
import re
from typing import NamedTuple

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


class Op(NamedTuple):
    start: int     # ps
    end: int       # ps
    self_ps: int
    scope: str
    name: str      # HLO instruction name, e.g. "fusion.12"


class Reduced(NamedTuple):
    window_ps: int
    devices: list          # per device: list[Op] inside the window
    busy_ps: list          # per device: union of op intervals
    gaps: list             # per device: [(start, end, host annotation)]


def _union(intervals) -> int:
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def ops_of(events, lo: int, hi: int) -> list[Op]:
    """Nest the device op events clipped to [lo, hi) and compute each op's
    self time and effective scope."""
    evs = []
    for ev in events:
        s, e = max(ev.start_ps, lo), min(ev.start_ps + ev.dur_ps, hi)
        if e > s:
            evs.append((s, -(e - s), e, ev))
    evs.sort()
    n = len(evs)
    children = [[] for _ in range(n)]
    stack = []
    for i, (s, _, e, _) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= evs[stack[-1]][2]:
            children[stack[-1]].append(i)
        stack.append(i)
    scope = [None] * n
    self_ps = [0] * n
    for i in range(n - 1, -1, -1):
        s, _, e, ev = evs[i]
        kids = children[i]
        self_ps[i] = (e - s) - _union((evs[k][0], evs[k][2]) for k in kids)
        sc = ev.scope
        if not sc and kids:
            big = max(kids, key=lambda k: evs[k][2] - evs[k][0])
            sc = scope[big]
        scope[i] = sc or ""
    return [Op(evs[i][0], evs[i][2], self_ps[i], scope[i],
               evs[i][3].name.split(" = ")[0].lstrip("%"))
            for i in range(n)]


def in_scope(scope: str, name: str) -> bool:
    """True when ``name`` is a component of the name stack ``scope`` —
    `mgmt_round` matches `jit(step)/vmap(mgmt_round)/while` and
    `hier_exchange` matches `.../hier_exchange/enclosure/...`."""
    return re.search(rf"(^|[/(]){re.escape(name)}([/):]|$)", scope) \
        is not None


def reduce(planes, window: tuple[int, int], host_spans) -> Reduced:
    """``window``: (start, end) in ps on the trace clock; ``host_spans``:
    [(start, end, name)] of the harness's own annotations."""
    lo, hi = window
    timeline = Timeline(host_spans)
    devs, busy, gaps = [], [], []
    for pl in sorted(planes, key=lambda p: p.name):
        m = DEVICE_PLANE.match(pl.name)
        if not m:
            continue
        events = [ev for ln in pl.lines if ln.name == OPS_LINE
                  for ev in ln.events]
        ops = ops_of(events, lo, hi)
        merged = _merged((o.start, o.end) for o in ops)
        busy.append(sum(e - s for s, e in merged))
        g, prev = [], lo
        for s, e in merged + [[hi, hi]]:
            if s > prev:
                g.append((prev, s, timeline.at((prev + s) // 2)))
            prev = max(prev, e)
        devs.append(ops)
        gaps.append(g)
    if not devs:
        raise ValueError("trace holds no /device:TPU:<n> plane")
    return Reduced(hi - lo, devs, busy, gaps)


class Timeline:
    """The innermost harness annotation open at each instant: nested host
    spans flattened into disjoint segments, looked up by bisection."""

    NONE = "no annotation"

    def __init__(self, host_spans):
        bounds = sorted({t for s, e, _ in host_spans for t in (s, e)})
        self.starts, self.names = [], []
        for a, b in zip(bounds, bounds[1:]):
            mid = (a + b) // 2
            best, best_len = self.NONE, None
            for s, e, name in host_spans:
                if s <= mid < e and (best_len is None or e - s < best_len):
                    best, best_len = name, e - s
            self.starts.append(a)
            self.names.append(best)
        self.end = bounds[-1] if bounds else 0

    def at(self, t: int) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        if i < 0 or t >= self.end:
            return self.NONE
        return self.names[i]


def scope_ps(red: Reduced, name: str) -> float | None:
    """Self time inside scope ``name``, averaged over the devices; None
    when no op of the trace ran in that scope."""
    tot, hit = [], False
    for ops in red.devices:
        sel = [o.self_ps for o in ops if in_scope(o.scope, name)]
        hit = hit or bool(sel)
        tot.append(sum(sel))
    return sum(tot) / len(tot) if hit else None


def top_ops(red: Reduced, k: int = 10) -> list:
    """[[scope or op name, seconds]] of the k largest self times, summed
    over devices, keyed by the name stack without its `jit(...)` root."""
    agg = {}
    for ops in red.devices:
        for o in ops:
            key = re.sub(r"^jit\([^)]*\)/", "", o.scope) if o.scope \
                else o.name
            key = key[:160]
            agg[key] = agg.get(key, 0) + o.self_ps
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ps / 1e12] for name, ps in top]


def top_gaps(red: Reduced, k: int = 10) -> list:
    """[[host annotation, seconds]] of idle time by what the host was
    doing, summed over devices, longest first."""
    agg = {}
    for g in red.gaps:
        for s, e, name in g:
            agg[name] = agg.get(name, 0) + (e - s)
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ps / 1e12] for name, ps in top]
