"""Device microseconds per simulated window inside the `fluid_transfer`
scope (grants turned into conserved capacity transfers,
`core/manager.py`), over the traced `simulate` call."""
import trace_reduce


def read(ctx):
    ps = trace_reduce.scope_ps(ctx["reduced"], "fluid_transfer")
    if ps is None:
        return None
    return ps / (ctx["windows"] * ctx["calls"]) / 1e6
