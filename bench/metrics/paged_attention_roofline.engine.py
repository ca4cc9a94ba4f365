"""Share (%) of the paged-attention kernel's roofline: the least time of
the traced steps' algorithmic work (live tokens only, `work.paged_attention`)
over the kernel's summed device time (the `paged_attention` scope). Nothing
to read when the kernel ran for no time."""
import peaks
import trace_reduce
import work


def read(ctx):
    kernel_ps = trace_reduce.scope_ps(ctx["reduced"], "paged_attention")
    if not kernel_ps:
        return None
    c = ctx["cfg"]
    least = 0.0
    for lengths in ctx["live_lengths"]:
        w = work.paged_attention(lengths, c.n_heads, c.kv_heads, c.head_dim,
                                 kv_bytes=ctx["kv_bytes"])
        least += peaks.least_time_s(w.flops, w.bytes, ctx["peaks"])[0]
    return 100.0 * least / (kernel_ps / 1e12)
