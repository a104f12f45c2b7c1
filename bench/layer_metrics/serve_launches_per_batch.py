"""Service answer: device programs launched per batch that start
inside an ``answer`` span and run at least one device operation (the
batched solve, its epilogue and the small programs a fetch of one
element runs), from the trace.

The reduction keeps each device operation with the program (HLO
module) it belongs to, not the programs' own events, so launches are
counted from the operations, and a program that runs no operation (a
squeeze that is only a bitcast) is not seen: on the v5e serve cell the
module events count 82 launches per batch, this reader 50 (PERF.md,
section 6).  A device runs one program at a time, so
one launch is a stretch of top-level operations (those no other
operation holds) of one module, and each launch runs its program's
first top-level operation once: a stretch holds as many launches as
it holds that operation."""
import bisect


def launches(ops, inside) -> int:
    """Launches, over the devices, whose first operation starts where
    ``inside(t)`` holds."""
    by_device = {}
    for o in ops:
        by_device.setdefault(o.device, []).append(o)
    count = 0
    for dev_ops in by_device.values():
        end, module, first = -1, None, None
        for o in sorted(dev_ops, key=lambda o: (o.start, -o.end)):
            if o.start < end and o.end <= end:
                continue                    # held by a top-level op
            end = o.end
            if o.module != module:
                module, first = o.module, o.name
            elif o.name != first:
                continue
            count += inside(o.start)
    return count


def read(ctx):
    tr = ctx["trace"]
    answers = [s for s in tr.spans if s.name == "answer"]
    if not answers or not tr.ops:
        return None
    starts = [s.start for s in answers]      # sorted, and never overlap

    def inside(t):
        i = bisect.bisect_right(starts, t) - 1
        return i >= 0 and t < answers[i].end
    return launches(tr.ops, inside) / len(answers) / len(tr.devices)
