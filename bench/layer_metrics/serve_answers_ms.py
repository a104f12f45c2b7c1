"""Service answer, host: milliseconds per batch inside the program's
``service.answers`` span (each query's results fetched from the
device, certified and built into an ``Answer``), from the program's
span record."""
from bench import program_record

SPAN = "service.answers"


def read(ctx):
    return program_record.span_ms_per_batch(ctx, SPAN)
