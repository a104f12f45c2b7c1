"""S3 sender: share of the full sweep the picks made, the row tiles
the sender swept (``GreediRISOut.sender_tiles_swept``; the lazy
kernel's own count) over picks times row tiles per pick
(``RoundUnits``), in percent: 100 for a solver that skips nothing."""
from bench import program_record


def read(ctx):
    c = program_record.round_counters(ctx)
    if not c:
        return None
    full = c["rounds"] * c["sender_picks"] * c["sender_tiles_per_pick"]
    return 100.0 * c["sender_tiles_swept"] / full
