"""Device-idle seconds per `simulate` call inside its `lax.scan` call
(`xbof.sim.scan`: JAX traces, lowers, compiles or loads from the cache,
and dispatches there), over the traced call."""
import program_spans


def read(ctx):
    return program_spans.idle_s_per_call(ctx, "xbof.sim.scan")
