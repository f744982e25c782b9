"""Ingress time per served multiply: the service's coalescing of the
queued requests by execution key, the session's validation of both
operands, and its structure and values fingerprints (program spans)."""

import program_spans

SPANS = ("spgemm.service.coalesce", "spgemm.session.validate",
         "spgemm.session.fingerprint")


def read(ctx):
    return program_spans.span_ms(ctx.window, SPANS)
