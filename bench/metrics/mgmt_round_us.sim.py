"""Device microseconds per simulated window inside the `mgmt_round` scope,
over the traced `simulate` call."""
import trace_reduce


def read(ctx):
    ps = trace_reduce.scope_ps(ctx["reduced"], "mgmt_round")
    if ps is None:
        return None
    return ps / (ctx["windows"] * ctx["calls"]) / 1e6
