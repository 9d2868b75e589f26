"""The plain reference: float64 mini-batch gradient descent on the same
rows, and the numbers that compare the program's training with it.

It imports nothing of the program and takes nothing the program made.
The batch schedule is the one the configuration states (Algorithm 1 as
the scheduler runs it): a permutation from the run's protocol seed,
consecutive batches, a fresh permutation when a batch would overrun.
The gradient operators are the paper's: eq. 7 (logistic, MacLaurin
d = WX/4 − Y/2, Y ∈ {−1, +1}) and eq. 8 (Poisson, d = e^{WX} − Y), with
the loss taken at the weights before each update.
"""
from __future__ import annotations

import math

import numpy as np

#: a leaf whose reference gradient is below this share of the median
#: leaf's moves by round-off alone and is left out of the norm gaps
NEGLIGIBLE_LEAF = 1e-3


def _lgamma1(y: np.ndarray) -> np.ndarray:
    return np.array([math.lgamma(v + 1.0) for v in y], np.float64)


GLMS = {
    "logistic": dict(
        d=lambda wx, y: 0.25 * wx - 0.5 * y,
        loss=lambda wx, y: float(np.mean(
            math.log(2.0) - 0.5 * y * wx + (y * wx) ** 2 / 8.0))),
    "poisson": dict(
        d=lambda wx, y: np.exp(wx) - y,
        loss=lambda wx, y: float(np.mean(
            np.exp(wx) - y * wx + _lgamma1(y)))),
}


def batches(n: int, batch: int, seed: int, steps: int):
    """Row indices of each of `steps` batches."""
    rng = np.random.default_rng(seed)
    order = rng.permutation(n)
    cursor = 0
    for _ in range(steps):
        if cursor + batch > n:
            order = rng.permutation(n)
            cursor = 0
        yield order[cursor:cursor + batch]
        cursor += batch


def train(blocks: list[tuple[str, np.ndarray]], y: np.ndarray, *, glm: str,
          lr: float, batch: int, seed: int, steps: int,
          keep=(0, 1, 3)) -> dict:
    """Run `steps` iterations.  Returns {"losses": [...], "w": {step:
    {party: weights}}} for the steps in `keep` and the last one."""
    model = GLMS[glm]
    X = np.concatenate([x for _, x in blocks], axis=1)
    cuts = np.cumsum([0] + [x.shape[1] for _, x in blocks])
    w = np.zeros(X.shape[1])

    def leaves(vec):
        return {nm: vec[cuts[i]:cuts[i + 1]].copy()
                for i, (nm, _) in enumerate(blocks)}

    out = {"losses": [], "w": {0: leaves(w)}}
    for step, idx in enumerate(batches(len(X), batch, seed, steps), 1):
        Xb, yb = X[idx], y[idx]
        wx = Xb @ w
        out["losses"].append(model["loss"](wx, yb))
        w = w - lr * (Xb.T @ model["d"](wx, yb)) / len(idx)
        if step in keep or step == steps:
            out["w"][step] = leaves(w)
    return out


def _norm_gaps(prog: dict, ref: dict, key) -> float:
    """Worst leaf of |‖prog‖ − ‖ref‖| over max(‖ref leaf‖, median ‖ref‖),
    leaving out leaves that are nought to rounding in the reference."""
    ref_n = {nm: float(np.linalg.norm(key(ref, nm))) for nm in ref["w"][0]}
    med = float(np.median(list(ref_n.values())))
    worst = 0.0
    for nm, rn in ref_n.items():
        if rn < NEGLIGIBLE_LEAF * med:
            continue
        pn = float(np.linalg.norm(key(prog, nm)))
        worst = max(worst, abs(pn - rn) / max(rn, med))
    return worst


def compare(prog: dict, ref: dict, lr: float) -> dict[str, float]:
    """The numbers `correct` is decided on.  `prog` and `ref` have the
    shape `train` returns, over the same number of iterations N:

    * loss_gap:   worst |loss − ref loss| / |ref loss| over all N losses;
    * grad_gap:   first gradient as the optimizer got it, (W0 − W1)/lr,
                  gap of norms, worst leaf;
    * change_gap: change of the weights over the first three steps,
                  gap of norms, worst leaf;
    * final_gap:  ‖ΔW − ΔW_ref‖ of the change over all N steps, worst
                  leaf, over max(‖ΔW_ref leaf‖, median leaf).
    """
    n = len(ref["losses"])
    if len(prog["losses"]) != n or n < 3:
        return {k: math.inf for k in
                ("loss_gap", "grad_gap", "change_gap", "final_gap")}
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))

    def step_diff(a, b):
        return lambda t, nm: (t["w"][a][nm] - t["w"][b][nm]) / (
            lr if (a, b) == (0, 1) else 1.0)

    grad_gap = _norm_gaps(prog, ref, step_diff(0, 1))
    change_gap = _norm_gaps(prog, ref, step_diff(3, 0))
    final = {nm: ref["w"][n][nm] - ref["w"][0][nm] for nm in ref["w"][0]}
    med = float(np.median([np.linalg.norm(v) for v in final.values()]))
    final_gap = max(
        float(np.linalg.norm(prog["w"][n][nm] - prog["w"][0][nm] - v))
        / max(float(np.linalg.norm(v)), med)
        for nm, v in final.items())
    return {"loss_gap": float(loss_gap), "grad_gap": grad_gap,
            "change_gap": change_gap, "final_gap": final_gap}
