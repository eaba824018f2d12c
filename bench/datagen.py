"""Inputs made from ``--seed`` in bulk on the host, on threads.

Rows are filled in chunks of about ``CHUNK_ELEMENTS`` elements; each chunk
draws from its own generator, keyed by the seed, a stream number and the
chunk's index.  So the same seed gives the same bits whatever the number of
threads or cores.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Callable

import numpy as np

CHUNK_ELEMENTS = 1 << 21


def generator(seed: int, *keys: int) -> np.random.Generator:
    """A generator keyed by the run's seed and ``keys``."""
    return np.random.default_rng(
        np.random.SeedSequence(seed % 2**64, spawn_key=tuple(keys)))


def fill_rows(rows: int, row_size: int, seed: int, stream: int,
              fill: Callable[[np.random.Generator, int, int], None]) -> None:
    """Call ``fill(rng, start, stop)`` for every chunk of ``rows`` rows of
    ``row_size`` elements."""
    step = max(1, CHUNK_ELEMENTS // row_size)
    chunks = range((rows + step - 1) // step)

    def one(c: int) -> None:
        fill(generator(seed, stream, c), c * step, min(rows, (c + 1) * step))

    with ThreadPoolExecutor(min(32, os.cpu_count() or 1)) as pool:
        list(pool.map(one, chunks))


def standard_normal(shape, seed: int, stream: int) -> np.ndarray:
    """float32 N(0, 1) array of ``shape``, filled by rows."""
    out = np.empty(shape, np.float32)

    def fill(rng, s, e):
        rng.standard_normal(out=out[s:e], dtype=np.float32)

    fill_rows(shape[0], int(np.prod(shape[1:])), seed, stream, fill)
    return out


def overlapping_gaussians(rows: int, features: int, sep: float, seed: int):
    """Two equally likely classes, y in {0, 1}, with X | y ~ N((y - 1/2) sep,
    I): the distribution of ``repro.glm.data.overlapping_gaussians``, whose
    classes overlap so that the logistic-regression optimum is finite."""
    X = np.empty((rows, features), np.float32)
    y = np.empty((rows, 1), np.float32)

    def fill(rng, s, e):
        label = rng.random(e - s) < 0.5
        y[s:e, 0] = label
        rng.standard_normal(out=X[s:e], dtype=np.float32)
        X[s:e] += (np.float32(sep) * (label.astype(np.float32) - 0.5))[:, None]

    fill_rows(rows, features, seed, 0, fill)
    return X, y
