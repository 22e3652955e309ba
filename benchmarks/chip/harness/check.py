"""How `correct` is decided: the served tokens against the plain reference.

After the window, a sample drawn from the seed of the requests the run
finished (always with the one that served the most tokens, one whose prompt
took whole-prompt prefill and one whose prompt took chunked prefill, where
the run has them) is replayed through the float32 reference: each prompt
followed by the tokens served for it, teacher-forced. For every served
token the reference gives the logits of the position that produced it;
the compared number is the widest normalized gap

    (reference's best logit - reference's logit of the served token)
    / std of the reference's logits at that position

over the sample. A greedy server that computes what the reference computes
serves the reference's best token, or one within rounding of it; a wrong
kernel, a lost cache or an altered token serves one far below it.
"""
from __future__ import annotations

import random
from typing import Dict, List, Optional, Tuple

import numpy as np

# the sample is packed into this many positions, so that the reference
# compiles one shape per configuration
REF_POSITIONS = 8192


def sample(finished: Dict[int, Tuple], seed: int, chunk: int,
           positions: int = REF_POSITIONS) -> List[int]:
    """Request ids to check. `finished`: rid -> (prompt, served tokens)."""
    rng = random.Random(int(seed) ^ 0x5EED)
    rids = sorted(finished)

    def cost(r):
        p, s = finished[r]
        return len(p) + len(s) - 1

    longest = max(rids, key=lambda r: (len(finished[r][1]),
                                       len(finished[r][0]), -r))
    picked, used = [longest], cost(longest)
    whole = [r for r in rids if len(finished[r][0]) <= chunk]
    chunked = [r for r in rids if len(finished[r][0]) > chunk]
    rest = list(rids)
    rng.shuffle(whole)
    rng.shuffle(chunked)
    rng.shuffle(rest)
    for group in (whole, chunked, rest):
        for r in group:
            if r in picked or used + cost(r) > positions:
                continue
            picked.append(r)
            used += cost(r)
            if group is not rest:
                break
    return picked


def widest(gaps: np.ndarray) -> Optional[float]:
    """The compared number, `logit_gap_max`: the widest gap, or None where
    no served token was checked."""
    return float(gaps.max()) if len(gaps) else None


def judge(gap: Optional[float], limit: float) -> bool:
    """`correct`: there is a reading, and it lies within the limit. A run
    with no finished request to check is not correct."""
    return gap is not None and gap <= limit


def served_gaps(ref, config: Dict, seed: int, prompts, served,
                control: bool = False) -> Dict[str, np.ndarray]:
    """Per served token, the normalized gap under the float32 reference
    (`gap`); with `control`, also the gap of the token the fp8 control
    ranks first at the same positions (`control_gap`)."""
    seqs, rows, targets = ref.served_rows(prompts, served)
    extra = []
    if control:
        h8 = ref.final_hidden(config, seed, seqs, rows, precision="fp8",
                              total=REF_POSITIONS)
        extra = [ref.head_scores(config, seed, h8, [], precision="fp8")
                 ["argmax"]]
        del h8
    h = ref.final_hidden(config, seed, seqs, rows, total=REF_POSITIONS)
    sc = ref.head_scores(config, seed, h, [targets] + extra)
    out = {"gap": (sc["max"] - sc["at"][0]) / sc["std"]}
    if control:
        out["control_gap"] = (sc["max"] - sc["at"][1]) / sc["std"]
    return out
