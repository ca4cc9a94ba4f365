"""Device microseconds per simulated window inside the `claim_sweep` scope
(the serial busiest-first claim sweeps of `core/manager.py`, nested in
`mgmt_round`), over the traced `simulate` call."""
import trace_reduce


def read(ctx):
    ps = trace_reduce.scope_ps(ctx["reduced"], "claim_sweep")
    if ps is None:
        return None
    return ps / (ctx["windows"] * ctx["calls"]) / 1e6
