"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and makes its inputs from the run's seed.

Kinds of mix (the ``kind`` key):

* ``packed_documents``: training batches of ``batch`` rows of ``seq``
  tokens.  Each row packs whole synthetic documents, separated by the end
  token, and cuts the last one; a document's length is drawn from
  ``doc_len``.  Labels are the row shifted by one token.
* ``request_waves``: waves of ``wave_size`` requests, each asking for
  ``new_tokens`` tokens.  A wave's prompt lengths are the ``wave_size``
  stratified quantiles of ``prompt_len``, the same in every wave and for
  every seed; the seed shuffles them over the wave and draws every token.
  So every seed sends the same work, in another order.

A length distribution is ``{"dist": "lognormal", "median": m, "sigma": s,
"min": lo, "max": hi}``: ``m * exp(s * z)`` for a standard normal ``z``,
rounded and clipped to ``[lo, hi]``.  Token ids are uniform over the
vocabulary without the end token.
"""

from __future__ import annotations

from statistics import NormalDist

import numpy as np


def _lengths(dist: dict, z: np.ndarray) -> np.ndarray:
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    raw = np.rint(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(raw, dist["min"], dist["max"]).astype(np.int64)


def draw_lengths(dist: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    return _lengths(dist, rng.standard_normal(n))


def stratified_lengths(dist: dict, n: int) -> np.ndarray:
    """The ``n`` quantiles at ``(i + 1/2) / n`` of a length distribution."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return _lengths(dist, z)


def token_ids(rng: np.random.Generator, n: int, vocab: int,
              eos: int) -> np.ndarray:
    """``n`` ids uniform over ``[0, vocab)`` without ``eos``."""
    ids = rng.integers(0, vocab - 1, size=n, dtype=np.int64)
    ids[ids >= eos] += 1
    return ids.astype(np.int32)


def packed_batches(mix: dict, rng: np.random.Generator, *, vocab: int,
                   eos: int):
    """Endless ``(tokens, labels)`` int32 batches of a packed_documents
    mix, each ``(batch, seq)``; every row is drawn afresh."""
    if mix["kind"] != "packed_documents":
        raise ValueError(f"mix kind {mix['kind']!r} is not packed_documents")
    batch, seq = mix["batch"], mix["seq"]
    while True:
        rows = np.empty((batch, seq + 1), np.int32)
        for r in range(batch):
            filled = 0
            while filled < seq + 1:
                n = int(draw_lengths(mix["doc_len"], rng, 1)[0])
                doc = token_ids(rng, n, vocab, eos)
                take = min(n, seq + 1 - filled)
                rows[r, filled:filled + take] = doc[:take]
                filled += take
                if filled < seq + 1:
                    rows[r, filled] = eos
                    filled += 1
        yield rows[:, :-1].copy(), rows[:, 1:].copy()


def request_waves(mix: dict, rng: np.random.Generator, *, vocab: int,
                  eos: int):
    """Endless waves of a request_waves mix: each a list of
    ``wave_size`` int32 prompts."""
    if mix["kind"] != "request_waves":
        raise ValueError(f"mix kind {mix['kind']!r} is not request_waves")
    lengths = stratified_lengths(mix["prompt_len"], mix["wave_size"])
    while True:
        order = rng.permutation(lengths)
        yield [token_ids(rng, int(n), vocab, eos) for n in order]


def wave_lengths(mix: dict) -> list[int]:
    """A request_waves mix's prompt lengths, the same in every wave."""
    return sorted(int(n) for n in
                  stratified_lengths(mix["prompt_len"], mix["wave_size"]))
