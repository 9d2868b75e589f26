"""Data for the benchmark's cells, kept here so that a change to the
program cannot change what a cell trains on.

Copied from the program: `credit_default` and `dvisits` from
`repro.data.synthetic`, `split_columns` and `replicate_provider` from
`repro.data.vertical`, `train_test_split` from `repro.data.synthetic`.
Only the seeds differ: every run draws its data from its own `--seed`.
"""
from __future__ import annotations

import numpy as np


def credit_default(n: int, d: int, seed: int):
    """n × d binary task shaped like the UCI default-of-credit-card-clients
    data: correlated bounded features, ~22 % positives, labels in {−1, +1}."""
    rng = np.random.default_rng(seed)
    k = 6
    factors = rng.normal(size=(n, k))
    mix = rng.normal(size=(k, d)) / np.sqrt(k)
    X = np.tanh(factors @ mix + 0.6 * rng.normal(size=(n, d)))
    w_true = rng.normal(size=d) * 1.1
    w_true[rng.permutation(d)[: d // 3]] = 0.0
    logits = X @ w_true + 0.3 * rng.normal(size=n)
    noise = rng.logistic(size=n) * 3.1
    thresh = np.quantile(logits + noise, 0.78)
    y = np.where(logits + noise > thresh, 1.0, -1.0)
    return X.astype(np.float64), y


def dvisits(n: int, d: int, seed: int):
    """n × d Poisson count task shaped like the Australian Health Survey
    doctor-visits data: mostly binary or bounded covariates, mean ≈ 0.30."""
    rng = np.random.default_rng(seed)
    X = np.concatenate([
        rng.binomial(1, rng.uniform(0.2, 0.7, size=8), size=(n, 8)),
        np.clip(rng.normal(0.4, 0.3, size=(n, 6)), 0, 1.5),
        rng.uniform(0, 1, size=(n, d - 14)),
    ], axis=1)
    w_true = rng.normal(size=d) * 0.35
    eta = X @ w_true
    eta = eta - eta.mean() + np.log(0.30)
    lam = np.exp(np.clip(eta, -6, 2.5))
    y = rng.poisson(lam).astype(np.float64)
    return X.astype(np.float64), y


GENERATORS = {"credit_default": credit_default, "dvisits": dvisits}


def train_test_split(X, y, ratio: float, seed: int):
    """The paper's train split: a seeded permutation, first `ratio` rows."""
    idx = np.random.default_rng(seed).permutation(len(X))
    tr = idx[:int(len(X) * ratio)]
    return X[tr], y[tr]


def split_columns(X: np.ndarray, n_parts: int) -> list[np.ndarray]:
    """Contiguous column blocks, C first (as FATE splits its VFL data)."""
    return [X[:, c] for c in np.array_split(np.arange(X.shape[1]), n_parts)]


def replicate_provider(parts: list[np.ndarray], n_parties: int
                       ) -> list[np.ndarray]:
    """Paper §5.1: 'in the multi-party case, we easily copy the data of
    party B1 to the new party'."""
    if len(parts) != 2:
        raise ValueError("the §5.1 copy starts from a C / B1 split")
    return [parts[0]] + [parts[1]] * (n_parties - 1)


def make_parties(data: dict, seeds) -> tuple[list[tuple[str, np.ndarray]],
                                             np.ndarray]:
    """Build the cell's training parties from a configuration's `data`
    block: generate, split 70/30, split columns, copy B1 per §5.1.
    Returns ([(name, X_p), …] with C first, y)."""
    gen = GENERATORS[data["generator"]]
    X, y = gen(data["rows"], data["features"], int(seeds.data))
    X, y = train_test_split(X, y, data["train_split"], int(seeds.split))
    names = data["parties"]
    parts = split_columns(X, data["column_blocks"])
    if len(names) > len(parts):
        parts = replicate_provider(parts, len(names))
    if len(parts) != len(names):
        raise ValueError(f"{len(names)} parties for {len(parts)} blocks")
    return list(zip(names, parts)), y
