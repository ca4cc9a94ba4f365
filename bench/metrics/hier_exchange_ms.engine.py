"""Device milliseconds per engine step inside `hier_exchange/*` scopes (the
topology exchange of `core/topology.py`, collectives included)."""
import trace_reduce


def read(ctx):
    ps = trace_reduce.scope_ps(ctx["reduced"], "hier_exchange")
    if ps is None or not ctx["steps"]:
        return None
    return ps / ctx["steps"] / 1e9
