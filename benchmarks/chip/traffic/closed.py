"""Closed loop: ``clients`` clients, each sending its next request as soon
as its last one completes.

Client ``i`` sends its first request at ``i / clients * start_spread_s``;
a spread of about one request's lifetime starts the loop near its steady
state.  Lengths come in rounds of ``round`` requests (by default
``clients``), each round the ``round`` mid-quantiles of the length
distributions in an order the seed draws; a round shorter than the
window's count of requests gives every seed's window nearly the same work.
"""

from __future__ import annotations

import traffic


class Mix(traffic.Mix):

    def __init__(self, spec, seed, ramp_s, seconds, vocab):
        super().__init__(spec, seed, ramp_s, seconds, vocab)
        c = spec["clients"]
        self.rng = traffic.seed_rng(seed)
        k = spec.get("round", c)
        self.grid = (traffic.quantiles(spec["prompt"], k),
                     traffic.quantiles(spec["output"], k))
        self.round = ([], [])
        self.next_send = [i / c * spec.get("start_spread_s", 0.0)
                          for i in range(c)]
        self.busy: dict[int, int] = {}

    def start(self, t0):
        super().start(t0)
        self.next_send = [t0 + s for s in self.next_send]

    def _sizes(self):
        if not self.round[0]:
            self.round = tuple(list(self.rng.permutation(g))
                               for g in self.grid)
        return self.round[0].pop(), self.round[1].pop()

    def take(self, now):
        out = []
        for c, t in enumerate(self.next_send):
            if t is not None and t <= now:
                req = self.make(now, *self._sizes())
                self.busy[req[0]] = c
                self.next_send[c] = None
                out.append(req)
        return out

    def next_due(self):
        ts = [t for t in self.next_send if t is not None]
        return min(ts) if ts else float("inf")

    def done(self, rid, now):
        c = self.busy.pop(rid, None)
        if c is not None:
            self.next_send[c] = now
