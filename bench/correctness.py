"""What decides ``correct`` for a served model.

Once the window has closed, a sample drawn from the seed of the requests it
finished, the longest among them, is run through the plain float32
reference: each prompt followed by the tokens that were served for it.  At
every served position the reading is how far the served token's logit lies
below the reference's best.  Greedy decoding picks the best logit of the
served (bf16) model, so a correct path lands on the reference's best or on
a near-tie of it; a wrong token, a wrong position or a lower precision lands
further down.  The widest gap over the sample is held to the cell's limit.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def sample(requests: Sequence, seed: int, *, max_requests: int,
           min_tokens: int) -> List:
    """The longest finished request, then others in an order drawn from the
    seed, until ``min_tokens`` served tokens or ``max_requests``."""
    done = [r for r in requests if r.result is not None and len(r.result)]
    if not done:
        return []
    done.sort(key=lambda r: (-(len(r.prompt) + len(r.result)), r.rid))
    out, rest = [done[0]], done[1:]
    order = np.random.default_rng(seed).permutation(len(rest))
    tokens = len(done[0].result)
    for i in order:
        if tokens >= min_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        tokens += len(rest[i].result)
    return out


def reference_inputs(reqs: Sequence):
    """(sequences, positions, tokens): each prompt with its served tokens but
    the last, the positions whose logits chose them, and the tokens."""
    seqs, pos, toks = [], [], []
    for r in reqs:
        L, out = len(r.prompt), np.asarray(r.result, np.int32)
        seqs.append(np.concatenate([r.prompt, out[:-1]]).astype(np.int32))
        pos.append(np.arange(L - 1, L - 1 + len(out)))
        toks.append(out)
    return seqs, pos, toks


def compare(ref, reqs: Sequence, *, control: bool = False) -> Dict[str, float]:
    """Readings over the sample: the widest gap of a served token (and, with
    ``control``, of the token the fp8 control puts first)."""
    seqs, pos, toks = reference_inputs(reqs)
    g = ref.logit_gaps(seqs, pos, toks, control=control)
    out = {"max_logit_gap": float(np.max(g["gap"])),
           "median_logit_gap": float(np.median(g["gap"])),
           "compared_tokens": int(len(g["gap"])),
           "compared_requests": len(reqs)}
    if control:
        out["control_max_logit_gap"] = float(np.max(g["control_gap"]))
    return out


__all__ = ["sample", "reference_inputs", "compare"]
