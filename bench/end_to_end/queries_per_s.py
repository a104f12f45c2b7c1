"""Answered queries over the window's time, which ends with the last
batch's answers on the host."""


def read(window) -> float:
    return window.attempted() / window.elapsed()
