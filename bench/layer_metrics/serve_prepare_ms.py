"""Service answer, host: milliseconds per batch inside the program's
``service.prepare`` span (the batch's query arrays built and copied to
the device), from the program's span record."""
from bench import program_record

SPAN = "service.prepare"


def read(ctx):
    return program_record.span_ms_per_batch(ctx, SPAN)
