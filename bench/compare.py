"""Whether the served tokens are what the model says.

Once the window has closed and the program's state is freed, a sample of
the served requests (drawn from the seed, the longest always in it) is run
through the configuration's plain reference: one full forward over each
prompt followed by its served tokens.  Tokens served earlier for prefixes
of a prompt (a context built in turns: a cold admission, then prefills
over restored prefixes) are read at their positions of the same forward.  At each served position the number
read is the gap by which the served token's reference logit lies below the
reference's best logit there (0 where the served token is the reference's
greedy choice).  Every served token is greedy, so a sound run reads small
gaps, set by rounding alone.

The control puts the reference in the program's place, computed in
float8 (the precision below the bfloat16 the configurations serve), and
reads the gap of the token that it ranks first.
"""

from __future__ import annotations

import numpy as np


def sample(requests, n: int, seed: int) -> list:
    """Up to ``n`` requests drawn from ``seed``: the longest (prompt plus
    served tokens), then one from each engine slot, then any."""
    reqs = [r for r in requests if r.tokens]
    if len(reqs) <= n:
        return reqs
    rng = np.random.default_rng(seed)
    longest = max(range(len(reqs)), key=lambda i: len(reqs[i].prompt) + len(reqs[i].tokens))
    pick = [longest]
    for slot in sorted({r.slot for r in reqs}, key=str):
        if len(pick) == n:
            break
        cands = [i for i, r in enumerate(reqs) if r.slot == slot and i not in pick]
        if cands:
            pick.append(int(rng.choice(cands)))
    rest = [i for i in range(len(reqs)) if i not in pick]
    pick += [int(i) for i in rng.choice(rest, n - len(pick), replace=False)]
    return [reqs[i] for i in sorted(pick)]


def _positions(req):
    """The sequence the reference reads, and each served token with the
    position whose logits chose it: the tokens served for the prompt's own
    prefixes (``req.earlier``) first, then the request's."""
    served = np.asarray(req.tokens, np.int64)
    tokens = np.concatenate([np.asarray(req.prompt, np.int64), served[:-1]])
    out_pos = np.arange(len(req.prompt) - 1, len(tokens))
    if req.earlier:
        pos, tok = (np.asarray(a, np.int64) for a in zip(*req.earlier))
        out_pos = np.concatenate([pos, out_pos])
        served = np.concatenate([tok, served])
    return tokens, out_pos, served


def served_gaps(ref, params, cfg: dict, reqs) -> np.ndarray:
    """The gap of every served token of ``reqs``, under the float32 reference."""
    gaps = []
    for req in reqs:
        tokens, out_pos, served = _positions(req)
        x = ref.hidden(params, cfg, tokens)
        best, z, _ = ref.logit_stats(params, cfg, x, out_pos, served[:, None])
        gaps.append(best - z[:, 0])
        del x
    return np.concatenate(gaps) if gaps else np.zeros(0)


def control_gaps(ref, params, cfg: dict, reqs) -> np.ndarray:
    """At the same positions, the gap of the token the float8 reference
    ranks first."""
    gaps = []
    for req in reqs:
        tokens, out_pos, served = _positions(req)
        xc = ref.hidden(params, cfg, tokens, fp8=True)
        _, _, top = ref.logit_stats(params, cfg, xc, out_pos, served[:, None], fp8=True)
        del xc
        x = ref.hidden(params, cfg, tokens)
        best, z, _ = ref.logit_stats(params, cfg, x, out_pos, top[:, None].astype(np.int64))
        gaps.append(best - z[:, 0])
        del x
    return np.concatenate(gaps) if gaps else np.zeros(0)


def numbers(gaps: np.ndarray) -> dict:
    if not gaps.size or not np.isfinite(gaps).all():
        return {"gap_max": float("inf"), "gap_mean": float("inf")}
    return {"gap_max": float(gaps.max()), "gap_mean": float(gaps.mean())}


def judge(nums: dict, failed: int, n_tokens: int, checks: dict) -> tuple[bool, dict]:
    """Each number compared beside its limit, and whether all hold: every
    gap the cell's checks give a limit at most that limit, no request
    failed, and at least ``min_checked_tokens`` served tokens compared."""
    out = {k: {"value": v, "limit": checks[k]["limit"]} for k, v in nums.items() if k in checks}
    out["failed_requests"] = {"value": failed, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in out.values())
    out["checked_tokens"] = {"value": n_tokens, "limit": checks["min_checked_tokens"]}
    return correct and n_tokens >= checks["min_checked_tokens"], out
