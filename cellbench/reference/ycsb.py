"""Plain reference for `ycsb_n4`: SimpleKVBC under YCSB workload A as a
history of versions, and the sparse merkle root of a state in plain
hashlib, nothing of the program imported.

The ledger starts with the preloaded records, one block of them after
another; every acknowledged update is then one block, whose id its
replies name. Blind overwrites of hot keys from many clients make the
final state depend on the order the cluster chose, so the state is the
updates applied in the order of those block ids, kept per key as a list
of versions (block, digest of the value): a read's check is then a
bisection.

A read is linearizable here if the value it returned is, for its key,
the value at some block between the last update acknowledged before the
read was sent (every replica of a reply quorum had applied it, and f+1
matching replies include one of them) and the last update sent before
the read returned (a replica can only have applied what was sent).

The tree (`kvbc/sparse_merkle.py`'s documented layout): 256 levels over
path = SHA-256(key); a leaf hashes H(0x00 || path || SHA-256(value)), an
inner node H(0x01 || left || right), an empty subtree its depth's
default (32 zero bytes at the leaves)."""
from __future__ import annotations

import bisect
import hashlib

DEPTH = 256
_DEFAULTS = [b""] * (DEPTH + 1)
_DEFAULTS[DEPTH] = b"\x00" * 32
for _d in range(DEPTH - 1, -1, -1):
    _DEFAULTS[_d] = hashlib.sha256(
        b"\x01" + _DEFAULTS[_d + 1] + _DEFAULTS[_d + 1]).digest()


def digest(value: bytes) -> bytes:
    return hashlib.sha256(value).digest()


class History:
    """Per key, its versions in block order: `blocks[key]` ascending
    and `digests[key]` beside them."""

    def __init__(self) -> None:
        self.blocks = {}
        self.digests = {}
        self.last_block = 0

    def apply(self, block: int, key: bytes, value_digest: bytes) -> None:
        """One write, in ascending block order."""
        if block < self.last_block:
            raise ValueError("writes are applied in block order")
        self.last_block = block
        self.blocks.setdefault(key, []).append(block)
        self.digests.setdefault(key, []).append(value_digest)

    def latest(self, key: bytes):
        d = self.digests.get(key)
        return d[-1] if d else None

    def state(self) -> dict:
        """{key: digest of its value} at the last block."""
        return {k: d[-1] for k, d in self.digests.items()}

    def read_is_linearizable(self, key: bytes, got, lo: int,
                             hi: int) -> bool:
        """Was `got` (a value's digest, or None for no value) the key's
        value at some block b, lo <= b <= hi?"""
        blocks = self.blocks.get(key, [])
        # versions current at some block of [lo, hi]: the one current
        # at lo, and each written after lo up to hi
        first = max(0, bisect.bisect_right(blocks, lo) - 1)
        last = bisect.bisect_right(blocks, hi)
        if got is None:
            return not blocks or blocks[0] > lo
        return got in self.digests.get(key, [])[first:last]


def bounds(acked, reads):
    """For each read (sent, done), (lo, hi): the greatest block among
    the updates (sent, done, block) acknowledged before it was sent,
    and among those sent before it returned (0 for none)."""
    by_done = sorted((d, b) for _s, d, b in acked)
    by_sent = sorted((s, b) for s, _d, b in acked)

    def prefix_max(rows):
        out, best = [], 0
        for _t, b in rows:
            best = max(best, b)
            out.append(best)
        return [t for t, _b in rows], out

    done_t, done_max = prefix_max(by_done)
    sent_t, sent_max = prefix_max(by_sent)
    out = []
    for sent, done in reads:
        i = bisect.bisect_left(done_t, sent)        # done < sent
        j = bisect.bisect_left(sent_t, done)        # sent < done
        out.append((done_max[i - 1] if i else 0, sent_max[j - 1] if j else 0))
    return out


# ---------------------------------------------------------------------
# the sparse merkle root, plainly
# ---------------------------------------------------------------------

def _subtree(depth: int, leaves) -> bytes:
    """The hash of the node at `depth` over `leaves`, (path bits, leaf
    hash) sorted by path, every one under that node."""
    if not leaves:
        return _DEFAULTS[depth]
    if len(leaves) == 1:
        bits, h = leaves[0]
        for d in range(DEPTH, depth, -1):
            sib = _DEFAULTS[d]
            if (bits >> (DEPTH - d)) & 1:
                h = hashlib.sha256(b"\x01" + sib + h).digest()
            else:
                h = hashlib.sha256(b"\x01" + h + sib).digest()
        return h
    # leaves sorted by path: those with bit `depth` clear come first
    split = bisect.bisect_left([(b >> (DEPTH - depth - 1)) & 1
                                for b, _h in leaves], 1)
    return hashlib.sha256(b"\x01" + _subtree(depth + 1, leaves[:split])
                          + _subtree(depth + 1, leaves[split:])).digest()


def _leaves(state: dict):
    out = []
    for key, value_digest in state.items():
        path = hashlib.sha256(key).digest()
        out.append((int.from_bytes(path, "big"),
                    hashlib.sha256(b"\x00" + path + value_digest).digest()))
    out.sort()
    return out


def merkle_root(state: dict) -> bytes:
    """The root over {key: SHA-256 of its value} (10,000 keys: about
    two seconds of one core)."""
    return _subtree(0, _leaves(state))
