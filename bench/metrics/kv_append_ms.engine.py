"""Device milliseconds per engine step inside the `kv_append` scope (every
active sequence's K/V row appended to the paged pool,
`serving/kv_pool.append_tokens`), from the traced steps."""
import trace_reduce


def read(ctx):
    ps = trace_reduce.scope_ps(ctx["reduced"], "kv_append")
    if ps is None or not ctx["steps"]:
        return None
    return ps / ctx["steps"] / 1e9
