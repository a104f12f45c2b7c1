"""95th percentile (nearest rank) of every query's latency in the
window: from its batch's submission to ``admit`` until its answer is
on the host."""
import math


def read(window) -> float:
    lat = sorted(window.latencies())
    return 1000.0 * lat[math.ceil(0.95 * len(lat)) - 1]
