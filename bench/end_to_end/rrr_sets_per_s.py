"""RRR sets per second: sets of every round in the window over the
window's time, which ends with its last round; whole rounds only."""


def read(window) -> float:
    return window.work() / window.elapsed()
