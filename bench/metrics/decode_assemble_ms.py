"""Assembly time per served multiply: ``from_coo`` of the kept entries
into the answer's CSC, the part of the decode that grows with the answer's
entries rather than with its tile stack (program span)."""

import program_spans

SPANS = ("spgemm.decode.assemble",)


def read(ctx):
    return program_spans.span_ms(ctx.window, SPANS)
