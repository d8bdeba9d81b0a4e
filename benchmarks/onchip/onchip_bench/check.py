"""The comparison that decides `correct`: served greedy tokens against the
plain float32 reference.

After the window a sample of finished greedy requests is drawn from the
seed, the longest among them always included.  The reference runs once
over each prompt followed by its served tokens, teacher-forced; at every
position that produced a served token, the gap is the reference's best
logit less its logit for the served token.  The widest gap over the
sample is compared with the cell's limit (`judge`).  The control puts
the reference computed in a lower precision in the program's place: its
own argmax stands where the served token was, and the same judgement
must find it not correct.
"""

from __future__ import annotations

import numpy as np

from onchip_bench.traffic import rng_for


def sample(finished: list, seed: int, k: int) -> list:
    """The longest finished greedy request and up to k - 1 others."""
    pool = [r for r in finished if r.item.greedy]
    if not pool:
        return []
    longest = max(pool, key=lambda r: (len(r.item.prompt) + len(r.tokens),
                                       r.item.index))
    rest = [r for r in pool if r is not longest]
    rng = rng_for(seed, "check")
    pick = rng.permutation(len(rest))[:max(0, k - 1)]
    return [longest] + [rest[j] for j in sorted(pick)]


def pairs(reqs: list) -> list[tuple[np.ndarray, np.ndarray, int]]:
    """(inputs, targets, first position to judge) of each request: the
    prompt and all but the last served token in, each next token out."""
    out = []
    for r in reqs:
        prompt = np.asarray(r.item.prompt, np.int32)
        served = np.asarray(r.tokens, np.int32)
        seq = np.concatenate([prompt, served])
        out.append((seq[:-1], seq[1:], len(prompt) - 1))
    return out


def _batched(ref, params, prs, quant, batch: int):
    res = []
    for j in range(0, len(prs), batch):
        res.extend(ref.run_batch(params, [(a, b) for a, b, _ in
                                          prs[j:j + batch]], quant))
    return res


def served_gaps(ref, params, reqs: list, batch: int) -> list[float]:
    """Per request, the widest gap of a served token below the
    reference's best logit."""
    prs = pairs(reqs)
    res = _batched(ref, params, prs, None, batch)
    return [float(np.max(best[p0:] - tgt[p0:]))
            for (best, tgt, _), (_, _, p0) in zip(res, prs)]


def control_gaps(ref, params, reqs: list, quant: str, batch: int
                 ) -> list[float]:
    """Per request, the widest gap of the token that `quant` puts first,
    read on the float32 reference at the same positions."""
    prs = pairs(reqs)
    ctl = _batched(ref, params, prs, quant, batch)
    top = [(a, am, p0) for (a, _, p0), (_, _, am) in zip(prs, ctl)]
    res = _batched(ref, params, [(a, am, p0) for a, am, p0 in top], None,
                   batch)
    return [float(np.max(best[p0:] - tgt[p0:]))
            for (best, tgt, _), (_, _, p0) in zip(res, top)]


def judge(ref, params, reqs: list, limit: float, quant=None
          ) -> tuple[float | None, bool]:
    """(widest gap, correct) of the served tokens, or with `quant` of the
    tokens the reference computed in that precision puts first.  No
    request to judge is not correct."""
    if not reqs:
        return None, False
    b = getattr(ref, "batch", 1)
    gaps = (served_gaps(ref, params, reqs, b) if quant is None
            else control_gaps(ref, params, reqs, quant, b))
    gap = max(gaps)
    return gap, gap <= limit
