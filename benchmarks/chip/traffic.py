"""Serving traffic, driven by a mix's data file.

A mix (``traffic/<name>.json``) names its ``kind`` and that kind's
parameters.  The generator of a kind is ``traffic/<kind>.py``, found by
name, so a new kind is a new file; it defines ``Mix(spec, seed, ramp_s,
seconds, vocab)``, a subclass of ``Mix`` below.  The kinds:

* ``poisson`` (``traffic/poisson.py``): open loop at a fixed rate.
* ``closed`` (``traffic/closed.py``): clients that each send their next
  request when the last one completes.

``prompt`` and ``output`` give the length distributions: ``lognormal``
(``median``, ``sigma``) or ``uniform``, clipped to ``[min, max]``.  Token
ids are drawn from the seed.
"""

from __future__ import annotations

import importlib.util
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

KINDS = Path(__file__).resolve().parent / "traffic"


def quantiles(dist: dict, n: int) -> np.ndarray:
    """n lengths at the mid-quantiles ``(i + 0.5) / n`` of ``dist``,
    clipped, as ints."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"] + 1)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.floor(x), dist["min"], dist["max"]).astype(np.int64)


def seed_rng(seed: int, *extra: int):
    """A generator from a seed of any size (and a stream number)."""
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, *extra])


def load(spec: dict, seed: int, ramp_s: float, seconds: float, vocab: int):
    """The mix's generator, from ``traffic/<kind>.py``."""
    path = KINDS / f"{spec['kind']}.py"
    if not path.is_file():
        raise ValueError(f"unknown traffic kind {spec['kind']!r}: "
                         f"no {path.name} in {KINDS}")
    mod_spec = importlib.util.spec_from_file_location(
        f"traffic_{spec['kind']}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.Mix(spec, seed, ramp_s, seconds, vocab)


class Mix:
    """Requests of one run: ``take(now)`` hands out those due by ``now``
    as ``(rid, due, prompt, max_new)``; ``next_due()`` says when the next
    can be; ``done(rid, now)`` tells the mix a request completed."""

    def __init__(self, spec: dict, seed: int, ramp_s: float, seconds: float,
                 vocab: int):
        self.spec = spec
        self.seed = seed
        self.ramp_s = ramp_s
        self.seconds = seconds
        self.vocab = vocab
        self.n = 0
        self.t0 = 0.0

    def start(self, t0: float) -> None:
        self.t0 = t0

    def make(self, due: float, prompt_len: int, max_new: int):
        """The next request, its prompt's tokens drawn from the seed."""
        i = self.n
        self.n += 1
        prompt = seed_rng(self.seed, i).integers(0, self.vocab,
                                                 int(prompt_len),
                                                 dtype=np.int32)
        return i, due, prompt, int(max_new)

    def take(self, now: float) -> list:
        raise NotImplementedError

    def next_due(self) -> float:
        return math.inf

    def done(self, rid: int, now: float) -> None:
        pass
