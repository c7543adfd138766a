"""The comparison that decides ``correct``.

Each committed round is compared with the reference's, by numbers that
rounding in another layout of the same bf16 computation moves little:

* ``loss_gap`` — the largest relative gap between a silo's reported
  training loss (its last local step) and the reference's;
* ``loss_median_gap`` — the median of those gaps, signed, over every
  silo and round, as a magnitude: steady where ``loss_gap`` swings with
  the one silo whose trajectory passed an early loss spike;
* ``delta_norm_gap`` — by the worst leaf and round, the gap between the
  norm of the program's change of the global and the reference's,
  against the reference's norm of that leaf or of the median leaf,
  whichever is larger;
* ``median_leaf_gap`` — by the worst round, the median over the leaves
  of that gap.

Element by element the two changes differ by tens of per cent: sixteen
AdamW steps turn any difference in bf16 rounding into another step
where a gradient is near zero, so no element-wise number is compared.

Leaves whose reference gradient is nought to rounding (under a
thousandth of the median leaf's, at every silo's first step) move by
round-off alone and are left out of the leaf number. A configuration
compares the numbers it gives a limit; ``compare`` reads them all.
"""
from __future__ import annotations

import numpy as np

from fedbench.reference import flat

NUMBERS = ("loss_gap", "loss_median_gap", "delta_norm_gap",
           "median_leaf_gap")


def _finite(x: float) -> float:
    """A number that is not finite compares as infinitely far off."""
    return x if np.isfinite(x) else float("inf")


def _norm(x) -> float:
    return float(np.linalg.norm(np.asarray(x, np.float64).ravel()))


def kept_leaves(grad_norms) -> list:
    """Leaves whose gradient is not nought to rounding anywhere."""
    names = list(grad_norms[0])
    top = {k: max(g[k] for g in grad_norms) for k in names}
    median = float(np.median(list(top.values())))
    return [k for k in names if top[k] >= 1e-3 * median]


def compare(prog: dict, ref: dict, leaves) -> dict:
    """``prog``/``ref``: ``{"init", "globals": [tree per round],
    "losses": [[per silo] per round]}``. Returns every number, and under
    ``"where"`` the round and silo or leaf each was read at."""
    loss_gap, loss_at, shifts = 0.0, None, []
    n = len(prog["losses"][0]) if prog["losses"] else 0
    for r, (lp, lr) in enumerate(zip(prog["losses"], ref["losses"])):
        for i, (p, q) in enumerate(zip(lp, lr)):
            shifts.append((p - q) / abs(q))
            gap = _finite(abs(p - q) / abs(q))
            if loss_at is None or gap > loss_gap:
                loss_gap, loss_at = gap, f"round {r} silo {i}"
    norm_gap, norm_at, median_gap, leaf_gaps = 0.0, None, 0.0, []
    prev_p, prev_r = flat(prog["init"]), flat(ref["init"])
    for r, (gp, gr) in enumerate(zip(prog["globals"], ref["globals"])):
        gp, gr = flat(gp), flat(gr)
        np_ = {k: _norm(np.asarray(gp[k]) - np.asarray(prev_p[k]))
               for k in leaves}
        nr = {k: _norm(np.asarray(gr[k]) - np.asarray(prev_r[k]))
              for k in leaves}
        median = float(np.median(list(nr.values())))
        gaps = {}
        for k in leaves:
            gap = gaps[k] = _finite(abs(np_[k] - nr[k]) / max(nr[k], median))
            if norm_at is None or gap > norm_gap:
                norm_gap, norm_at = gap, f"round {r} {k}"
        median_gap = max(median_gap, float(np.median(list(gaps.values()))))
        leaf_gaps.append({k: round(v, 5) for k, v in gaps.items()})
        prev_p, prev_r = gp, gr
    return {"loss_gap": loss_gap, "delta_norm_gap": norm_gap,
            "loss_median_gap": _finite(abs(float(np.median(shifts)))),
            "median_leaf_gap": median_gap,
            "loss_gaps": [[round(x, 6) for x in shifts[r * n:(r + 1) * n]]
                          for r in range(len(prog["losses"]))],
            "leaf_gaps": leaf_gaps,
            "where": {"loss_gap": loss_at, "delta_norm_gap": norm_at}}


def judge(numbers: dict, limits: dict) -> dict:
    """``{name: {"value", "limit"}}`` for every number that has a limit."""
    return {k: {"value": numbers[k], "limit": lim}
            for k, lim in limits.items()}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
