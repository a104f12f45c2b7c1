"""Service answer, host: milliseconds per batch inside the program's
``service.dispatch`` span (the batched solve and its epilogue
enqueued, the jit cache looked up), from the program's span record."""
from bench import program_record

SPAN = "service.dispatch"


def read(ctx):
    return program_record.span_ms_per_batch(ctx, SPAN)
