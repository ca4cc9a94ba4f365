"""Host seconds per `simulate` call spent tracing, lowering and compiling
(or loading from the compile cache), from JAX's monitoring events during
the traced call."""


def read(ctx):
    return ctx["compile_s"] / ctx["calls"]
