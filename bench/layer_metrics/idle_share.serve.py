"""Device idle share of the serve window: 1 - busy / window, from the
trace (the window runs from the first batch's admit span to the last
span)."""


def read(ctx):
    tr = ctx["trace"]
    if not tr.count("answer"):
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
