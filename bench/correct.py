"""`correct` for a serving cell: served tokens against the plain reference.

Once the window has closed, a sample of the requests the program finished
is drawn from the seed, the longest among them, until it holds
`sample_tokens` served tokens or `sample_requests` requests. The
reference (reference/plain.py) reads each prompt with its served tokens,
and the number compared is the widest gap by which a served token's
reference logit lies below the reference's best at that position.
"""
from __future__ import annotations

import numpy as np


def sample(reqs, seed: int, tokens: int, max_requests: int) -> list:
    done = [r for r in reqs if r.finish_reason == "length"]
    if not done:
        return []
    done.sort(key=lambda r: (-r.n_generated, -r.prompt_len, r.rid))
    pick, rest = [done[0]], done[1:]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
    for i in rng.permutation(len(rest)):
        if (sum(r.n_generated for r in pick) >= tokens
                or len(pick) >= max_requests):
            break
        pick.append(rest[int(i)])
    return pick


def served_gap(weights, sizes: dict, picked, pad_to: int,
               control: bool = False):
    """(widest served gap, widest control gap or None, tokens compared)."""
    from .reference.plain import served_gaps

    worst, worst_ctl, n = 0.0, 0.0, 0
    for r in picked:
        g, c = served_gaps(weights, sizes, r.prompt, r.tokens, pad_to,
                           control=control)
        worst = max(worst, float(g.max()))
        if c is not None:
            worst_ctl = max(worst_ctl, float(c.max()))
        n += len(g)
    return worst, (worst_ctl if control else None), n
