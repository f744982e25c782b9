"""Decode time per served multiply: the prune scan of the output tile
stack and the assembly of the answer's CSC (program spans)."""

import program_spans

SPANS = ("spgemm.decode.prune", "spgemm.decode.assemble")


def read(ctx):
    return program_spans.span_ms(ctx.window, SPANS)
