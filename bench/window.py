"""What a measured window holds: its timed items, in order."""
from __future__ import annotations

from dataclasses import dataclass, field


@dataclass
class Item:
    start: float            # host clock, seconds
    end: float
    requests: int           # requests this item answered (rounds, queries)
    work: int               # units of work (RRR sets, queries)
    latencies: list = field(default_factory=list)  # seconds, per request


@dataclass
class Window:
    unit: str               # what an item is ("rounds", "batches")
    t0: float = 0.0
    t1: float = 0.0
    items: list = field(default_factory=list)

    def elapsed(self) -> float:
        return self.t1 - self.t0

    def attempted(self) -> int:
        return sum(i.requests for i in self.items)

    def work(self) -> int:
        return sum(i.work for i in self.items)

    def latencies(self) -> list:
        return [x for i in self.items for x in i.latencies]
