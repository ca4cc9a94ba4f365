"""The program's own host spans (`repro.obs.export.SPANS`, kind "host") in
this process's profiler trace, and the device idle time they overlap.

The harness writes the trace of a `--trace 1` run under
`.bench_out/<cell>.<pid>/trace`; the program's spans (`xbof.*`) sit on the
same host plane and clock as the harness's `bench_*` annotations. A program
without such spans gives an empty list, and the readers report nothing.
"""
from __future__ import annotations

import functools
import os
import pathlib

import trace_reduce
import xspace

ROOT = pathlib.Path(__file__).resolve().parent.parent
PREFIX = "xbof."


def this_trace(root: pathlib.Path = ROOT) -> pathlib.Path | None:
    """The newest trace file this process wrote, or None."""
    files = sorted((root / ".bench_out").glob(
        f"*.{os.getpid()}/trace/**/*.xplane.pb"))
    return files[-1] if files else None


@functools.lru_cache(maxsize=4)
def host_events(path: str) -> tuple:
    """((start_ps, end_ps, name), ...) of the host-plane events of the
    trace at ``path`` whose names start with `xbof.`, in start order."""
    return tuple(sorted(
        (e.start_ps, e.start_ps + e.dur_ps, e.name)
        for p in xspace.read(path) if p.name.startswith("/host")
        for ln in p.lines for e in ln.events if e.name.startswith(PREFIX)))


def spans(name: str) -> list:
    """[(start_ps, end_ps)] of the span ``name`` in this process's trace."""
    path = this_trace()
    if path is None:
        return []
    return [(s, e) for s, e, n in host_events(str(path)) if n == name]


def idle_overlap_ps(red, intervals) -> float:
    """Device idle time (`red.gaps`) inside the union of ``intervals``,
    averaged over the devices. A gap counts only where it overlaps, so a
    gap that straddles two spans is split between them."""
    union = trace_reduce._merged(intervals)
    per_dev = []
    for gaps in red.gaps:
        tot = 0
        for gs, ge, _ in gaps:
            for s, e in union:
                tot += max(0, min(ge, e) - max(gs, s))
        per_dev.append(tot)
    return sum(per_dev) / len(per_dev)


def idle_s_per_call(ctx, name: str) -> float | None:
    """Seconds of device idle time under span ``name``, per traced call;
    None where the trace holds no such span."""
    iv = spans(name)
    if not iv:
        return None
    return idle_overlap_ps(ctx["reduced"], iv) / ctx["calls"] / 1e12
