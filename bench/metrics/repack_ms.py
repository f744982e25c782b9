"""Values-only repack time per served multiply: re-blockizing the
operands whose values changed and putting the fresh payload stacks on the
device (program spans)."""

import program_spans

SPANS = ("spgemm.repack.blockize", "spgemm.repack.h2d")


def read(ctx):
    return program_spans.span_ms(ctx.window, SPANS)
