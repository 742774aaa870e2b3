"""The comparisons that decide ``correct``, each number against its limit.

Training (the reference follows the program's steps from the same seed):

* ``loss_gap_nats``: the largest gap between the program's and the
  reference's loss over the first three steps;
* ``grad1_norm_gap``: the worst leaf's gap between the program's and the
  reference's norm of the first gradient, as a share of the reference's
  norm of that leaf or of the median leaf, whichever is larger;
* ``update_norm_gap``: the same for the norm of each leaf's change over
  all the steps.

Leaves whose first gradient in the reference is under a thousandth of the
median leaf's are left out of both norm gaps: their change is round-off.

Serving: ``served_logit_gap``, the widest gap by which a served token's
reference logit lies below the reference's best at that position.
"""

from __future__ import annotations

import math
import statistics

GRAD_FLOOR = 1e-3      # of the median leaf's first-gradient norm
LOSS_STEPS = 3


def counted_leaves(ref_grad1: dict[str, float]) -> list[str]:
    median = statistics.median(ref_grad1.values())
    return sorted(k for k, g in ref_grad1.items() if g >= GRAD_FLOOR * median)


def norm_gap(prog: dict[str, float], ref: dict[str, float],
             leaves: list[str]) -> tuple[float, str]:
    """(worst relative gap of norms, its leaf) over ``leaves``."""
    median = statistics.median(ref[k] for k in leaves)
    worst, where = 0.0, ""
    for k in leaves:
        gap = abs(prog[k] - ref[k]) / max(ref[k], median)
        if not math.isfinite(gap):
            return math.inf, k
        if gap > worst:
            worst, where = gap, k
    return worst, where


def train_checks(prog: dict, ref: dict, limits: dict) -> dict:
    """Checks of a training run; ``prog`` and ``ref`` hold ``losses``,
    ``grad1_norms`` and ``update_norms``."""
    missing = set(ref["grad1_norms"]) ^ set(prog["grad1_norms"])
    if missing:
        raise ValueError(f"leaves differ between program and reference: "
                         f"{sorted(missing)[:5]}")
    n = min(LOSS_STEPS, len(ref["losses"]))
    loss_gap = max(abs(p - r) for p, r in
                   zip(prog["losses"][:n], ref["losses"][:n], strict=True))
    if not all(math.isfinite(x) for x in prog["losses"]):
        loss_gap = math.inf
    leaves = counted_leaves(ref["grad1_norms"])
    g_gap, g_leaf = norm_gap(prog["grad1_norms"], ref["grad1_norms"], leaves)
    u_gap, u_leaf = norm_gap(prog["update_norms"], ref["update_norms"],
                             leaves)
    return {
        "loss_gap_nats": {"value": loss_gap,
                          "limit": limits["loss_gap_nats"]},
        "grad1_norm_gap": {"value": g_gap, "limit": limits["grad1_norm_gap"],
                           "leaf": g_leaf},
        "update_norm_gap": {"value": u_gap,
                            "limit": limits["update_norm_gap"],
                            "leaf": u_leaf},
    }


def logit_gaps(ref_logits, tokens) -> list[float]:
    """Per position: the reference's best logit minus that of the token
    chosen there.  ``ref_logits`` (K, vocab), ``tokens`` (K,)."""
    best = ref_logits.max(axis=-1)
    chosen = ref_logits[range(len(tokens)), tokens]
    return [float(g) for g in best - chosen]


def serve_checks(gaps: list[float], limits: dict) -> dict:
    worst = max(gaps) if gaps else math.inf
    return {"served_logit_gap": {"value": worst,
                                 "limit": limits["served_logit_gap"]}}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
