"""Device-idle seconds per `simulate` call while the host prepares it
(`xbof.sim.prepare`: workload packing, `want_frac`, events, the initial
state), over the traced call."""
import program_spans


def read(ctx):
    return program_spans.idle_s_per_call(ctx, "xbof.sim.prepare")
