"""The query stream of a traffic mix, drawn from ``--seed``.

A mix lists query templates: a filter column, either a range ``width``
(the number of values in ``[lo, hi]``, with ``lo`` uniform over the
column's domain) or ``point`` (``lo == hi`` taken from a random good row),
a projection, and ``per_round``: how many of the mix's clients send that
template in each round.  Every round holds the same templates in the same
order, and the seed draws only the ranges and points: a round's shape
decides which scan group a flush serves first and so where the median
latency falls, so a shape drawn from the seed would make the seed change
the work (a first version that dealt templates from shuffled decks read
p50 spreads of 11-17% on the synthetic mix, 1,718 ms on one seed against
2,100-2,370 on the others).
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Query:
    template: str
    column: str
    lo: int
    hi: int
    projection: tuple[str, ...]


class QueryStream:
    def __init__(self, traffic: dict, cfg: dict, cols: dict,
                 bad: np.ndarray, seed: int, salt: int = 0):
        self.templates = list(traffic["templates"])
        self._domain = {c["name"]: (int(c["lo"]), int(c["hi"]))
                        for c in cfg["columns"]}
        for t in self.templates:
            if t["filter"] not in self._domain:
                raise KeyError(f"template {t['name']}: no column "
                               f"{t['filter']!r} in {cfg['name']}")
        self._round = [t for t in self.templates
                       for _ in range(int(t["per_round"]))]
        self._cols = cols
        self._good = np.flatnonzero(~bad)
        self._rng = np.random.default_rng([seed, salt])

    @property
    def clients(self) -> int:
        return len(self._round)

    def draw(self, t: dict) -> Query:
        col = t["filter"]
        if t.get("point"):
            v = int(self._cols[col][self._good[
                self._rng.integers(len(self._good))]])
            lo = hi = v
        else:
            d_lo, d_hi = self._domain[col]
            width = int(t["width"])
            lo = int(self._rng.integers(d_lo, d_hi - width + 1))
            hi = lo + width - 1
        return Query(t["name"], col, lo, hi, tuple(t["projection"]))

    def next_round(self) -> list:
        """One query per client, in the mix's template order."""
        return [self.draw(t) for t in self._round]
