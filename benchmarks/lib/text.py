"""Seeded printable text of an exact length.

The program's tokenizer here is the byte tokenizer padded to the model's
vocabulary (no tokenizer ships in this image), so a character is a token and
a prompt of N tokens is a text of N - 1 characters after the BOS token.
"""

from __future__ import annotations

import numpy as np

_LETTERS = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz", dtype=np.uint8)


def words(rng: np.random.Generator, n_chars: int) -> str:
    """``n_chars`` characters of lower-case words of 2 to 9 letters."""
    if n_chars <= 0:
        return ""
    buf = _LETTERS[rng.integers(0, 26, n_chars)].copy()
    pos = int(rng.integers(2, 10))
    while pos < n_chars - 1:
        buf[pos] = 32
        pos += 1 + int(rng.integers(2, 10))
    return buf.tobytes().decode("ascii")


def lognormal_int(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """``n`` integers from a lognormal with the given median and sigma,
    clipped to [min, max]: the distribution's ``n`` evenly spaced quantiles in
    a seeded order, so that every seed offers the same amount of work and
    only its order differs (a steadier window than ``n`` free draws)."""
    from statistics import NormalDist

    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    x = np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    return rng.permutation(x)
