"""YCSB CoreWorkload request generator, read by every ``*.json`` mix here.

A mix file names this generator (``"generator": "ycsb"``) and gives
CoreWorkload's parameters: ``readproportion`` (only 1.0, workload C, is
served yet), ``requestdistribution`` (``zipfian`` or ``uniform``),
``zipfian_constant``, plus the harness's own: ``multiget_keys`` per
request, and ``clients`` and ``loop`` (one closed-loop client). Every
multi-get is drawn afresh, as CoreWorkload draws every read: the client
calls :func:`draw` once per request on its generator seeded from
``--seed``, so a seed fixes the whole stream of requests.

``zipfian`` is YCSB's ScrambledZipfianGenerator, copied from
``site.ycsb.generator.ZipfianGenerator`` / ``ScrambledZipfianGenerator``:
a Zipfian rank over YCSB's fixed item space of 10^10 items (with its
precomputed zeta, 26.46902820178302, at the constant 0.99), scrambled onto
the loaded records by FNV-1a 64 (``site.ycsb.Utils.fnvhash64``). The
hottest record thus takes 1/zeta = 3.78% of the draws whatever the record
count. Unlike CoreWorkload, which draws over ``recordcount + 1`` and
rejects the one key not yet loaded, the hash is taken modulo the record
count.
"""

from __future__ import annotations

import numpy as np

ITEM_COUNT = 10_000_000_000          # ScrambledZipfianGenerator.ITEM_COUNT
ZETAN = 26.46902820178302            # its zeta(ITEM_COUNT, 0.99)
USED_ZIPFIAN_CONSTANT = 0.99
FNV_OFFSET_BASIS_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)


def fnvhash64(v: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64``: FNV-1a over the 8 low-order-first bytes
    of a long, then ``Math.abs`` of the signed result."""
    v = np.asarray(v, np.int64).view(np.uint64).copy()
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    for _ in range(8):
        h ^= v & np.uint64(0xFF)
        h *= FNV_PRIME_64
        v >>= np.uint64(8)
    return np.abs(h.view(np.int64))


def zeta(n: int, theta: float) -> float:
    return float(np.sum(1.0 / np.arange(1, n + 1, dtype=np.float64)
                        ** theta))


def zipfian_ranks(u: np.ndarray, items: int, theta: float,
                  zetan: float) -> np.ndarray:
    """``ZipfianGenerator.nextLong`` for uniform draws ``u`` in [0, 1)."""
    zeta2 = zeta(2, theta)
    alpha = 1.0 / (1.0 - theta)
    eta = (1 - (2.0 / items) ** (1 - theta)) / (1 - zeta2 / zetan)
    uz = u * zetan
    ranks = (items * np.power(eta * u - eta + 1, alpha)).astype(np.int64)
    ranks = np.where(uz < 1.0 + 0.5 ** theta, 1, ranks)
    return np.where(uz < 1.0, 0, ranks)


def scrambled_zipfian(n_records: int, size, rng,
                      theta: float = USED_ZIPFIAN_CONSTANT) -> np.ndarray:
    """Record numbers in [0, n_records), YCSB's default request
    distribution."""
    if theta != USED_ZIPFIAN_CONSTANT:
        raise ValueError("only YCSB's precomputed zeta at 0.99 is copied")
    ranks = zipfian_ranks(rng.random(size), ITEM_COUNT + 1, theta, ZETAN)
    return fnvhash64(ranks) % n_records


def draw(mix: dict, n_records: int, rng, count: int = 1) -> np.ndarray:
    """The next ``count`` multi-gets from ``rng``: (count, multiget_keys)
    record numbers in [0, n_records)."""
    if mix.get("readproportion") != 1.0:
        raise ValueError("only read-only mixes (YCSB workload C) are served")
    if mix.get("clients") != 1 or mix.get("loop") != "closed":
        raise ValueError("the harness drives one closed-loop client")
    size = (count, mix["multiget_keys"])
    dist = mix["requestdistribution"]
    if dist == "zipfian":
        return scrambled_zipfian(n_records, size, rng,
                                 mix.get("zipfian_constant",
                                         USED_ZIPFIAN_CONSTANT))
    if dist == "uniform":
        return rng.integers(0, n_records, size)
    raise ValueError(f"unknown requestdistribution {dist!r}")
