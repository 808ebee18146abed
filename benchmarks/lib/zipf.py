"""YCSB's request distributions, ported from its Java generators
(site.ycsb.generator.ZipfianGenerator / ScrambledZipfianGenerator and
site.ycsb.Utils.fnvhash64), so that key skew is the source's own.

The scrambled generator draws from a zipfian over ITEM_COUNT = 10^10
items with YCSB's precomputed zeta, hashes the draw with FNV-1a and
folds it onto the real key range: the popular keys are spread over the
key space instead of clustered at its start.
"""
from __future__ import annotations

import random

FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211
_MASK = (1 << 64) - 1

ZIPFIAN_CONSTANT = 0.99
ITEM_COUNT = 10_000_000_000
ZETAN = 26.46902820178302       # zeta(ITEM_COUNT, 0.99), as YCSB ships it


def fnvhash64(val: int) -> int:
    """Utils.fnvhash64: FNV-1a over the value's 8 octets, low first, in
    Java's signed 64-bit arithmetic, then Math.abs."""
    h = FNV_OFFSET_BASIS_64
    for _ in range(8):
        h ^= val & 0xFF
        val >>= 8
        h = (h * FNV_PRIME_64) & _MASK
    if h >= 1 << 63:            # Java long is signed: abs of the negative
        h = (1 << 64) - h
    return h


class Zipfian:
    """ZipfianGenerator(0, items - 1, theta, zetan): Gray et al.'s
    rejection-free draw; item 0 is the most popular."""

    def __init__(self, items: int, rng: random.Random,
                 theta: float = ZIPFIAN_CONSTANT, zetan: float = None):
        self.items = items
        self.rng = rng
        self.theta = theta
        self.zetan = zetan if zetan is not None else \
            sum(1.0 / (i ** theta) for i in range(1, items + 1))
        self.alpha = 1.0 / (1.0 - theta)
        zeta2 = 1.0 + 0.5 ** theta
        self.eta = (1 - (2.0 / items) ** (1 - theta)) \
            / (1 - zeta2 / self.zetan)

    def next(self) -> int:
        u = self.rng.random()
        uz = u * self.zetan
        if uz < 1.0:
            return 0
        if uz < 1.0 + 0.5 ** self.theta:
            return 1
        return int(self.items * (self.eta * u - self.eta + 1) ** self.alpha)


class ScrambledZipfian:
    """ScrambledZipfianGenerator(0, items - 1): zipfian popularity,
    popular items scattered over [0, items)."""

    def __init__(self, items: int, rng: random.Random):
        self.items = items
        self.gen = Zipfian(ITEM_COUNT, rng, ZIPFIAN_CONSTANT, ZETAN)

    def next(self) -> int:
        return fnvhash64(self.gen.next()) % self.items


class Uniform:
    def __init__(self, items: int, rng: random.Random):
        self.items = items
        self.rng = rng

    def next(self) -> int:
        return self.rng.randrange(self.items)


def key_name(n: int) -> str:
    """CoreWorkload.buildKeyName with hashed insert order."""
    return "user" + str(fnvhash64(n))
