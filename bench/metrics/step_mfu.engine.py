"""The whole engine step's share (%) of the chip's peak: the least time of
the traced steps' algorithmic work (`work.engine_step`: q/k/v projections,
K/V appends, attention over live tokens) over the traced steps' wall
seconds."""
import peaks
import work


def read(ctx):
    if not ctx["steps"] or ctx["span_s"] <= 0:
        return None
    c = ctx["cfg"]
    least = 0.0
    for lengths in ctx["live_lengths"]:
        w = work.engine_step(len(lengths), lengths, ctx["d_model"],
                             c.n_heads, c.kv_heads, c.head_dim,
                             kv_bytes=ctx["kv_bytes"])
        least += peaks.least_time_s(w.flops, w.bytes, ctx["peaks"])[0]
    return 100.0 * least / ctx["span_s"]
