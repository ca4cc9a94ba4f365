"""Device milliseconds per engine step inside the `mgmt_round` scope (the
management round of `core/manager.py`), from the traced steps."""
import trace_reduce


def read(ctx):
    ps = trace_reduce.scope_ps(ctx["reduced"], "mgmt_round")
    if ps is None or not ctx["steps"]:
        return None
    return ps / ctx["steps"] / 1e9
