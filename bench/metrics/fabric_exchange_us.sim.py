"""Device microseconds per simulated window inside `hier_exchange/*` scopes
(the fabric settlement of `core/topology.py`), over the traced call."""
import trace_reduce


def read(ctx):
    ps = trace_reduce.scope_ps(ctx["reduced"], "hier_exchange")
    if ps is None:
        return None
    return ps / (ctx["windows"] * ctx["calls"]) / 1e6
