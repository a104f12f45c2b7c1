"""Peak device memory of the round cell in GB (1e9 bytes):
``memory_peak_bytes`` of the result's device block, the larger of the
allocator's peak and the largest program's arguments, outputs and
temporaries on the fullest chip."""


def read(ctx):
    peak = ctx["device"].get("memory_peak_bytes")
    return peak / 1e9 if peak else None
