"""Device milliseconds per engine step inside the `route` scope (the §4.4
redirect split and its LINK_BW byte cap) and the `admit` scope (slot
admission), from the traced steps."""
import trace_reduce


def read(ctx):
    parts = [trace_reduce.scope_ps(ctx["reduced"], s)
             for s in ("route", "admit")]
    if all(p is None for p in parts) or not ctx["steps"]:
        return None
    return sum(p or 0.0 for p in parts) / ctx["steps"] / 1e9
