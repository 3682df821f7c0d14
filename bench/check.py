"""The comparison that decides ``correct``: served tokens against the plain
reference.

Once the window has closed and the engine is freed, a seeded sample of the
requests the run served (always with the one that received the most
tokens) is run through the float32 reference, one sequence at a time:
each prompt followed by the tokens the engine served for it.  At each
served token the reference's best logit minus its logit of the served
token is that token's gap; greedy decoding within rounding of the
reference reads near 0, a wrong token reads the spread of the logits.  The
number compared is the widest gap.

The control puts the reference computed in fp8 in the program's place: at
the same positions, the gap of the token that the fp8 forward ranks first.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

SAMPLE = 8


def sample(records: Sequence, seed: int, k: int = SAMPLE) -> List:
    """Up to ``k`` served requests: the one with the most tokens and a
    seeded draw of the others (only those served at least one token)."""
    served = sorted((r for r in records if r.token_times),
                    key=lambda r: r.rid)
    if not served:
        return []
    longest = max(served, key=lambda r: (len(r.token_times), -r.rid))
    rest = [r for r in served if r is not longest]
    rng = np.random.default_rng([seed, 5])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def sequences(recs: Sequence) -> List[Tuple[np.ndarray, List[int]]]:
    """(prompt, served tokens) of each record."""
    return [(np.asarray(r.job.prompt, np.int32), r.tokens()) for r in recs]


def gaps(ref, weights, config: dict, seqs, length: int,
         control: bool = False) -> Dict[str, float]:
    """Widest gap of the served tokens (and, with ``control``, of the
    tokens the fp8 reference ranks first) over ``seqs``.

    Every sequence is padded to ``length`` positions so that one compiled
    reference serves all of them; causal attention keeps the padding out.
    """
    widest, widest_ctrl, n = 0.0, 0.0, 0
    for prompt, served in seqs:
        if not served:
            continue
        toks = np.zeros((length,), np.int32)
        full = np.concatenate([prompt, np.asarray(served, np.int32)])
        toks[:len(full)] = full
        rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
        lg = np.asarray(ref.logits(weights, toks, config))[rows]
        best = lg.max(-1)
        widest = max(widest, float(
            (best - lg[np.arange(len(served)), served]).max()))
        n += len(served)
        if control:
            lc = np.asarray(ref.logits(weights, toks, config, "fp8"))[rows]
            pick = lc.argmax(-1)
            widest_ctrl = max(widest_ctrl, float(
                (best - lg[np.arange(len(served)), pick]).max()))
    out = {"served_logit_gap": widest, "compared_tokens": float(n)}
    if control:
        out["control_logit_gap"] = widest_ctrl
    return out


def verdict(readings: Dict[str, float], limits: Dict[str, float]):
    """``correct`` and the checks, each number beside its limit."""
    checks = {k: {"value": readings[k], "limit": lim}
              for k, lim in limits.items()}
    correct = readings.get("compared_tokens", 0) > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    return correct, checks
