"""YCSB's core workload as traffic for SimpleKVBC: the mix kind
`ycsb_core`, read from `traffic/<mix>.json` as `generate.py` reads its
kinds. `generate.py`'s contract holds: everything is a function of
`--seed`, so the same seed gives the same records, operations, keys and
values, whatever the system's speed.

What is YCSB's (Cooper et al., "Benchmarking Cloud Serving Systems
with YCSB", SoCC 2010; `site.ycsb.workloads.CoreWorkload`, recalled
offline, the recalled values listed under the configuration's
`assumed`):

- a record's key is "user" + the decimal FNV-64 hash of its number
  (`orderedinserts=false`: `Utils.fnvhash64`, Java's signed arithmetic
  and `Math.abs`);
- a record is `fieldcount` fields of `fieldlength` printable bytes; a
  SimpleKVBC key holds one value, so the record is their concatenation,
  and an update rewrites all of it (`writeallfields=true`);
- `requestdistribution=zipfian` is `ScrambledZipfianGenerator`: a
  Zipfian over 10^10 items with constant 0.99 and the precomputed zeta
  `ZETAN`, its draw hashed with FNV-64 onto the key space. CoreWorkload
  builds the generator over [0, recordcount] (its space for inserts,
  none in workload A) and draws again past the last record loaded.
"""
from __future__ import annotations

import random

# ScrambledZipfianGenerator's constants
ZIPFIAN_CONSTANT = 0.99
ITEM_COUNT = 10_000_000_000
ZETAN = 26.46902820178302

_FNV_OFFSET_64 = 0xCBF29CE484222325
_FNV_PRIME_64 = 1099511628211
_M64 = (1 << 64) - 1

# RandomByteIterator's alphabet: ' ' plus six random bits
_PRINTABLE = bytes(32 + (b & 63) for b in range(256))


def fnvhash64(val: int) -> int:
    """`Utils.fnvhash64`: FNV-1a over the value's eight low bytes, with
    Java's wrapping `long` and `Math.abs` (which leaves Long.MIN_VALUE
    negative)."""
    h = _FNV_OFFSET_64
    val &= _M64
    for _ in range(8):
        h ^= val & 0xFF
        val >>= 8
        h = (h * _FNV_PRIME_64) & _M64
    signed = h - (1 << 64) if h >> 63 else h
    return signed if signed == -(1 << 63) else abs(signed)


def _java_rem(a: int, b: int) -> int:
    """Java's `%`: the sign of the dividend."""
    r = abs(a) % b
    return -r if a < 0 else r


def key_name(record: int) -> bytes:
    """`CoreWorkload.buildKeyName` with `orderedinserts=false` and
    `zeropadding=1`."""
    return b"user%d" % fnvhash64(record)


def zeta(n: int, theta: float) -> float:
    return sum(1.0 / (i + 1) ** theta for i in range(n))


class ScrambledZipfian:
    """`ScrambledZipfianGenerator(0, records)` as CoreWorkload builds it
    for workload A; `next(rng)` is the record number of one operation.
    The Zipfian under it is `ZipfianGenerator(0, ITEM_COUNT, 0.99,
    ZETAN)`: 10^10 + 1 items."""

    def __init__(self, records: int) -> None:
        self.records = records
        self.itemcount = records + 1
        items = ITEM_COUNT + 1
        theta = ZIPFIAN_CONSTANT
        self.alpha = 1.0 / (1.0 - theta)
        self.zetan = ZETAN
        self.eta = ((1.0 - (2.0 / items) ** (1.0 - theta))
                    / (1.0 - zeta(2, theta) / ZETAN))
        self.items = items
        self._second = 1.0 + 0.5 ** theta

    def zipfian(self, u: float) -> int:
        """`ZipfianGenerator.nextLong` for the uniform draw `u`."""
        uz = u * self.zetan
        if uz < 1.0:
            return 0
        if uz < self._second:
            return 1
        return int(self.items * (self.eta * u - self.eta + 1) ** self.alpha)

    def next(self, rng: random.Random) -> int:
        while True:
            rec = _java_rem(fnvhash64(self.zipfian(rng.random())),
                            self.itemcount)
            # CoreWorkload.nextKeynum: past the last record loaded (and
            # Long.MIN_VALUE's negative remainder), draw again
            if 0 <= rec < self.records:
                return rec


def field_bytes(rng: random.Random, n: int) -> bytes:
    return rng.randbytes(n).translate(_PRINTABLE)


class Records:
    """The records loaded before the run, as the configuration's file
    sizes them: `recordcount` (key, value) pairs, the value `fieldcount`
    × `fieldlength` printable bytes drawn from the seed alone."""

    def __init__(self, cfg: dict, seed: int) -> None:
        self.count = cfg["recordcount"]
        self.value_bytes = cfg["fieldcount"] * cfg["fieldlength"]
        self.seed = seed
        self.keys = [key_name(r) for r in range(self.count)]

    def value(self, record: int) -> bytes:
        return field_bytes(random.Random(f"{self.seed}/ycsb/record/{record}"),
                           self.value_bytes)

    def blocks(self, per_block: int):
        """The load in blocks of `per_block` records, record order:
        lists of (key, value)."""
        for lo in range(0, self.count, per_block):
            yield [(self.keys[r], self.value(r))
                   for r in range(lo, min(lo + per_block, self.count))]


class Client:
    """One closed-loop YCSB client: `op(i)` is its i-th operation,
    ("read", record, None) or ("update", record, value). The client's
    operations come from one seeded stream, asked for in order, so the
    i-th is the same on every run of the seed."""

    def __init__(self, mix: dict, seed: int, index: int,
                 chooser: ScrambledZipfian, value_bytes: int) -> None:
        self.index = index
        self._read_p = mix["readproportion"]
        self._value_bytes = value_bytes
        self._chooser = chooser
        self._rng = random.Random(f"{seed}/ycsb/client/{index}")
        self._next = 0

    def op(self, i: int):
        assert i == self._next, (i, self._next)
        self._next += 1
        rng = self._rng
        read = rng.random() < self._read_p
        rec = self._chooser.next(rng)
        return (("read", rec, None) if read
                else ("update", rec, field_bytes(rng, self._value_bytes)))


def clients(mix: dict, records: Records, seed: int) -> list:
    """Every client of the mix over the loaded `records`."""
    assert mix["kind"] == "ycsb_core", mix["kind"]
    assert (mix["requestdistribution"], mix["zipfian_constant"]) \
        == ("zipfian", ZIPFIAN_CONSTANT), "ZETAN is zeta(10^10, 0.99)"
    assert mix["readproportion"] + mix["updateproportion"] == 1
    chooser = ScrambledZipfian(records.count)
    return [Client(mix, seed, c, chooser, records.value_bytes)
            for c in range(mix["clients"])]
