"""Open loop: Poisson arrivals at ``rate_per_s``, whatever the server does.

In the ramp, in the window and in a tail as long as the window,
``round(rate_per_s * length)`` requests arrive at times drawn uniformly
from the seed: a Poisson process given its number of arrivals, with all of
its clustering.  Their lengths are that many mid-quantiles of the length
distributions, in an order the seed draws, so every seed's window holds the
same work at other times and in another order.  The tail keeps the load on
while the window's last requests wait for their first tokens.
"""

from __future__ import annotations

import math

import numpy as np

import traffic


class Mix(traffic.Mix):

    def __init__(self, spec, seed, ramp_s, seconds, vocab):
        super().__init__(spec, seed, ramp_s, seconds, vocab)
        rng = traffic.seed_rng(seed)
        rate = spec["rate_per_s"]
        due, prompts, outputs = [], [], []
        for start, length in ((0.0, ramp_s), (ramp_s, seconds),
                              (ramp_s + seconds, seconds)):
            n = round(rate * length)
            due.append(start + np.sort(rng.uniform(0.0, length, n)))
            prompts.append(rng.permutation(traffic.quantiles(spec["prompt"],
                                                             n)))
            outputs.append(rng.permutation(traffic.quantiles(spec["output"],
                                                             n)))
        self.due = np.concatenate(due)
        self.prompts = np.concatenate(prompts)
        self.outputs = np.concatenate(outputs)

    def take(self, now):
        out = []
        while self.n < len(self.due) and self.t0 + self.due[self.n] <= now:
            k = self.n
            out.append(self.make(self.t0 + self.due[k], self.prompts[k],
                                 self.outputs[k]))
        return out

    def next_due(self):
        return (self.t0 + self.due[self.n] if self.n < len(self.due)
                else math.inf)
