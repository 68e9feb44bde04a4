"""The comparison that decides ``correct``.

After the window, a sample of the requests the engine finished, drawn from
the seed and always holding the longest of them, is run through the plain
reference of the configuration's model family (its ``logits``) once, each
prompt with the tokens the engine served after it.  Each served token is
greedy, so the reference's logit for it should sit at the top of that
position's logits, short of rounding.  The number compared is the widest
gap, over every served token of the sample, by which the served token's
reference logit lies below the reference's best.

The control runs the same reference in float8 in the program's place: at
each of the same positions it reads the gap of the token that float8 puts
first.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def sample(finished: list[dict], seed: int, tokens: int,
           most: int) -> list[dict]:
    """The longest finished request, then others in a seeded order until
    ``tokens`` served tokens or ``most`` requests."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: -(r["prompt_len"] + len(r["out"])))
    picked = [order[0]]
    rest = order[1:]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    for i in rng.permutation(len(rest)):
        if sum(len(r["out"]) for r in picked) >= tokens or \
                len(picked) >= most:
            break
        picked.append(rest[i])
    return picked


def inputs(reqs: list[dict]):
    """Token sequences and the positions whose logits chose each served
    token."""
    seqs, positions, served = [], [], []
    for r in reqs:
        prompt, out = np.asarray(r["prompt"]), np.asarray(r["out"])
        seqs.append(np.concatenate([prompt, out[:-1]]).astype(np.int32))
        positions.append(np.arange(len(prompt) - 1,
                                   len(prompt) - 1 + len(out)))
        served.append(out)
    return seqs, positions, served


def widest_gap(ref_logits, chosen) -> float:
    """Max over positions of (best reference logit - reference logit of the
    chosen token)."""
    worst = 0.0
    for lg, tok in zip(ref_logits, chosen):
        tok = jnp.asarray(np.asarray(tok), jnp.int32)
        gap = lg.max(axis=-1) - jnp.take_along_axis(lg, tok[:, None], 1)[:, 0]
        worst = max(worst, float(gap.max()))
    return worst


def gaps(family, key, model, served_spec, reqs, control: bool = False):
    """(widest gap of the served tokens, widest gap of the tokens float8
    puts first at the same positions or None without ``control``, tokens
    compared), by the reference of ``family``."""
    seqs, positions, served = inputs(reqs)
    ref = family.logits(key, model, served_spec, seqs, positions)
    low = None
    if control:
        fp8 = family.logits(key, model, served_spec, seqs, positions,
                            precision="fp8")
        low = widest_gap(ref, [jnp.argmax(lg, axis=-1) for lg in fp8])
    return widest_gap(ref, served), low, int(sum(len(s) for s in served))
