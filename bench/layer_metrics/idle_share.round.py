"""Device idle share of the round window: 1 - busy / window, from the
trace (the window runs from the first round span to the last)."""


def read(ctx):
    tr = ctx["trace"]
    if not tr.count("round"):
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
