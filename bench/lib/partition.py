"""Client partitions, with the same per-client sizes for every seed.

The schemes are those of ``repro.core.partition`` (the paper's §5.2):
``quantity`` gives each client rows of ``alpha`` classes, each class split
evenly among the clients that took it, with every class kept by at least
one client; ``dirichlet`` spreads each class over the clients by
Dir(alpha) proportions.

What differs is where the randomness goes. The structure (which client
takes which classes, the Dirichlet proportions) is drawn once from the
configuration's fixed ``structure_seed``, so the slab shape and the
amount of work are the same in every run; the run's seed permutes the
clients and the class labels, and draws every row value. A slab whose
padded length moved with the seed would compile anew for each seed.

Each function returns a ``(clients, classes)`` table of row counts.
"""
from __future__ import annotations

import numpy as np


def class_totals(n_rows: int, n_classes: int) -> np.ndarray:
    """Rows per class: as even as ``n_rows`` allows."""
    base = np.full(n_classes, n_rows // n_classes, np.int64)
    base[: n_rows % n_classes] += 1
    return base


def quantity_counts(totals: np.ndarray, n_clients: int, alpha: int,
                    rng: np.random.Generator) -> np.ndarray:
    n_classes = len(totals)
    choices = [set(rng.choice(n_classes, size=min(alpha, n_classes),
                              replace=False).tolist())
               for _ in range(n_clients)]
    covered = set().union(*choices)
    for m in range(n_classes):
        if m not in covered:
            least = min(range(n_clients), key=lambda c: len(choices[c]))
            choices[least].add(m)
    counts = np.zeros((n_clients, n_classes), np.int64)
    for m in range(n_classes):
        takers = [c for c in range(n_clients) if m in choices[c]]
        for c, part in zip(takers, np.array_split(np.arange(totals[m]),
                                                  len(takers))):
            counts[c, m] = len(part)
    return counts


def dirichlet_counts(totals: np.ndarray, n_clients: int, alpha: float,
                     rng: np.random.Generator,
                     min_size: int = 2) -> np.ndarray:
    while True:
        counts = np.zeros((n_clients, len(totals)), np.int64)
        for m, total in enumerate(totals):
            props = rng.dirichlet(alpha * np.ones(n_clients))
            cuts = (np.cumsum(props) * total).astype(int)[:-1]
            counts[:, m] = np.diff(np.concatenate([[0], cuts, [total]]))
        if counts.sum(axis=1).min() >= min_size:
            return counts


def client_counts(part: dict, n_rows: int, n_classes: int, n_clients: int,
                  seed: int) -> tuple[np.ndarray, np.ndarray]:
    """The (clients, columns) count table of one run and the class that
    each column holds: the fixed structure of ``part`` (``scheme``,
    ``alpha``, ``structure_seed``) with its clients permuted by ``seed``
    and its columns given to classes in an order drawn from ``seed``. The
    multiset of client sizes is the same for every seed."""
    totals = class_totals(n_rows, n_classes)
    srng = np.random.default_rng(part["structure_seed"])
    if part["scheme"] == "quantity":
        counts = quantity_counts(totals, n_clients, int(part["alpha"]), srng)
    elif part["scheme"] == "dirichlet":
        counts = dirichlet_counts(totals, n_clients, float(part["alpha"]),
                                  srng)
    else:
        raise ValueError(f"unknown partition scheme {part['scheme']!r}")
    rng = np.random.default_rng(seed)
    return counts[rng.permutation(n_clients)], rng.permutation(n_classes)
