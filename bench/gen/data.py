"""Seeded input pools for the benchmark's TM cells.

The two families are copies of the repository's synthetic TM generators
(``repro.data.synthetic.binarized_images`` / ``bow_documents``), kept here so
that the yardstick does not move when the program's copies do. Each family
also returns its class *prototypes*: one ``(o,)`` 0/1 feature vector per
class (the image template, or the class's signal words). The TA state
generator (``gen/state.py``) draws its clauses from them, so the state stands
in for a machine that has learnt the classes of the data it is served.

Everything here is NumPy on the host and depends only on the seed.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator for one use (``stream``) of a run's seed."""
    return np.random.default_rng([int(seed), int(stream)])


def image_templates(rng: np.random.Generator, n_classes: int, o: int,
                    active: float) -> np.ndarray:
    """(m, o) bool class templates with ``active`` of the bits set."""
    return rng.uniform(size=(n_classes, o)) < active


def templated_images(templates: np.ndarray, n: int, *, noise: float,
                     rng: np.random.Generator):
    """``n`` noisy samples of the class templates → (x uint8, y int32)."""
    n_classes, o = templates.shape
    y = rng.integers(0, n_classes, n).astype(np.int32)
    flip = rng.uniform(size=(n, o)) < noise
    x = templates[y] ^ flip
    return x.astype(np.uint8), y


def bow_signal(rng: np.random.Generator, n_classes: int, o: int,
               signal: int) -> np.ndarray:
    """(m, signal) word ids that mark each class."""
    return rng.integers(0, o, (n_classes, signal))


def bow_documents(sig: np.ndarray, n: int, o: int, *, active_frac: float,
                  rng: np.random.Generator):
    """IMDb-like sparse bag-of-words → (x (n, o) uint8, y (n,) int32):
    ``active_frac`` of the vocabulary as background words, plus a quarter
    of the class's signal words (drawn with repetition)."""
    n_classes, signal = sig.shape
    n_active = max(4, int(active_frac * o))
    y = rng.integers(0, n_classes, n).astype(np.int32)
    x = np.zeros((n, o), np.uint8)
    rows = np.arange(n)[:, None]
    x[rows, rng.integers(0, o, (n, n_active))] = 1
    take = rng.integers(0, signal, (n, max(2, signal // 4)))
    x[rows, sig[y[:, None], take]] = 1
    return x, y


def pool(data: dict, n_classes: int, o: int, n: int, seed: int):
    """The cell's input pool from its configuration's ``data`` block.

    Returns ``(x (n, o) uint8, y (n,) int32, prototypes (m, o) uint8)``.
    """
    family = data["family"]
    if family == "binarized_images":
        templates = image_templates(rng_for(seed, 1), n_classes, o,
                                    data["active"])
        x, y = templated_images(templates, n, noise=data["noise"],
                                rng=rng_for(seed, 2))
        return x, y, templates.astype(np.uint8)
    if family == "bow_documents":
        sig = bow_signal(rng_for(seed, 1), n_classes, o, data["signal"])
        x, y = bow_documents(sig, n, o, active_frac=data["active_frac"],
                             rng=rng_for(seed, 2))
        proto = np.zeros((n_classes, o), np.uint8)
        proto[np.arange(n_classes)[:, None], sig] = 1
        return x, y, proto
    raise ValueError(f"unknown data family {family!r}")
