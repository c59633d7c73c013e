"""What decides ``correct``: the served tokens against the float32
reference.

After the window has closed and the program's state is freed, a sample of
the requests the window finished, drawn from the seed and holding the
longest of them, is run once through ``reference.py`` over its prompt and
its served tokens.  The number compared is the widest gap by which a
served token's reference logit lies below the reference's best at the
position that produced it.  Greedy decoding in bfloat16 picks the
reference's best or a near-tie of it; a wrong page, position, head or
token puts a served token far below the best.  The limit is the
configuration's ``correct.max_gap`` (see ``PERF.md`` for the readings it
was set from).
"""

from __future__ import annotations

import numpy as np

from chipbench import reference, weights


def sample(tracks, seed: int, want_tokens: int) -> list:
    """The longest finished request, then others in an order drawn from
    the seed, until ``want_tokens`` served tokens are in the sample."""
    done = [t for t in tracks
            if t.done and len(t.req.tokens) == t.item.max_new]
    if not done:
        return []
    done.sort(key=lambda t: (len(t.req.tokens), t.item.prompt.size,
                             t.item.rid))
    picked = [done.pop()]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
    for i in rng.permutation(len(done)):
        if sum(len(t.req.tokens) for t in picked) >= want_tokens:
            break
        picked.append(done[i])
    return picked


def compare(a, seed: int, picked: list) -> dict:
    """Widest gap over the sample, with the reference's own weights drawn
    again from the seed."""
    w = weights.draw(a, weights.seed_key(seed))
    per = []
    for t in picked:
        gaps, _ = reference.served_gaps(a, w, t.item.prompt, t.req.tokens)
        per.append(float(np.max(gaps)))
    return {"widest_gap": max(per) if per else float("nan"),
            "requests": len(picked),
            "tokens": int(sum(len(t.req.tokens) for t in picked)),
            "per_request": per}
