"""The one traffic generator. A mix is a data file, `traffic/<mix>.json`,
of parameters that this module reads; the program sees only the
requests it makes. Everything is a function of `--seed`: the same seed
gives the same keys, values, principals, messages, signatures, shares
and spoiled positions, whatever the system's speed (how far down its
sequence a client gets in a window is the system's doing).

Two kinds of mix exist, named by the file's `kind`:

`kv_writes`   closed-loop SimpleKVBC writers, in classes; a class is
              `clients` clients that each keep one message in flight, a
              message being `writes_per_message` signed write
              transactions (1: a plain request; more: upstream's
              ClientBatchRequestMsg) of `pairs_per_write` pairs.
`sig_flood`   slots of a signature flood on one replica's crypto plane:
              `messages_per_slot` ed25519 messages from `principals`
              distinct principals, and `shares_per_slot` threshold-BLS
              shares of a `threshold`-of-`signers` certificate.
"""
from __future__ import annotations

import hashlib
import random

from cellbench.reference import bls as ref_bls
from cellbench.reference import ed25519 as ref_ed


def _h(*parts) -> bytes:
    return hashlib.sha256("/".join(map(str, parts)).encode()).digest()


# ---------------------------------------------------------------------
# kv_writes
# ---------------------------------------------------------------------

class KvClient:
    """One closed-loop writer: `message(i)` is its i-th message, a list
    of write transactions, each a list of (key, value) pairs. Keys are
    distinct over the whole run, so the final state does not depend on
    the order concurrent clients are served in."""

    def __init__(self, mix: dict, seed: int, index: int, cls: dict,
                 cls_name: str) -> None:
        self.index = index
        self.cls = cls_name
        self.writes_per_message = cls["writes_per_message"]
        self._pairs = cls.get("pairs_per_write", 1)
        self._klen, self._vlen = mix["key_bytes"], mix["value_bytes"]
        self._seed = seed

    def message(self, i: int) -> list:
        return [[(_h("k", self._seed, self.index, i, w, j)[:self._klen],
                  _h("v", self._seed, self.index, i, w, j)[:self._vlen])
                 for j in range(self._pairs)]
                for w in range(self.writes_per_message)]


def kv_clients(mix: dict, seed: int) -> list:
    """Every client of the mix, classes in the file's order."""
    assert mix["kind"] == "kv_writes", mix["kind"]
    out = []
    for name, cls in mix["classes"].items():
        for _ in range(cls["clients"]):
            out.append(KvClient(mix, seed, len(out), cls, name))
    return out


# ---------------------------------------------------------------------
# sig_flood
# ---------------------------------------------------------------------

class Flood:
    """The flood's principals, dealer and slots. Signing keys and the
    dealer's polynomial are the generator's own (plain references), so
    what the program is given is public keys, messages, signatures and
    shares — and the reference can judge every one of them."""

    def __init__(self, mix: dict, seed: int) -> None:
        assert mix["kind"] == "sig_flood", mix["kind"]
        self.mix, self.seed = mix, seed
        self.n = mix["principals"]
        self.signers = [ref_ed.Signer(_h("ed25519", seed, p))
                        for p in range(self.n)]
        self.threshold, self.total = mix["threshold"], mix["signers"]
        self.poly = ref_bls.Polynomial(
            int.from_bytes(_h("poly", seed, i) + _h("poly2", seed, i),
                           "big") % (ref_bls.R - 1) + 1
            for i in range(self.threshold))
        self.digests = [_h("digest", seed, d)
                        for d in range(mix["digests"])]
        # shares[d][i-1]: signer i's share over digest d
        self.shares = [ref_bls.shares_of(self.poly, self.total, d)
                       for d in self.digests]

    def public_keys(self) -> dict:
        return {p: s.public for p, s in enumerate(self.signers)}

    def secret_shares(self) -> list:
        """f(1..signers): key material for the system's set-up (its
        share public keys); the comparison never reads it."""
        return [self.poly.at(i) for i in range(1, self.total + 1)]

    def slot(self, j: int):
        """Slot j: (items, digest index, offered shares). Items are
        (principal, message, signature); messages carry the slot's
        number, so nothing repeats over a run. A seeded few items are
        spoiled: forged (the message altered after signing), truncated
        (a 40-byte signature) or duplicated (a copy of the item before
        it). Offered shares are (id, 48 bytes): `shares_per_slot` honest
        ones from a seeded set of signers, in seeded order, and among
        them `junk_shares` that an accumulator has to drop (an id out
        of range, a short encoding, an x that is on no point)."""
        mix, rng = self.mix, random.Random(f"{self.seed}/slot/{j}")
        items = []
        for i in range(mix["messages_per_slot"]):
            p = i % self.n
            msg = b"preprepare/%d/%d/" % (j, i) + rng.randbytes(24)
            items.append((p, msg, self.signers[p].sign(msg)))
        kinds = (["forged"] * mix["forged"] + ["truncated"] * mix["truncated"]
                 + ["duplicate"] * mix["duplicates"])
        for i, kind in zip(rng.sample(range(1, len(items)), len(kinds)),
                           kinds):
            p, msg, sig = items[i]
            if kind == "forged":
                items[i] = (p, msg + b"!", sig)
            elif kind == "truncated":
                items[i] = (p, msg, sig[:40])
            else:
                items[i] = items[i - 1]
        d = j % len(self.digests)
        ids = rng.sample(range(1, self.total + 1), mix["shares_per_slot"])
        offered = [(i, self.shares[d][i - 1]) for i in ids]
        taken = set(ids)
        spare = [i for i in range(1, self.total + 1) if i not in taken]
        junk = [(self.total + 1 + rng.randrange(50), self.shares[d][0]),
                (rng.choice(spare), self.shares[d][1][:30]),
                (rng.choice(spare), b"\x9f" + b"\xff" * 47)]
        for share in junk[:mix["junk_shares"]]:
            offered.insert(rng.randrange(len(offered)), share)
        return items, d, offered

    # -- what the reference says of a slot --

    def reference_verdicts(self, items) -> list:
        return [ref_ed.verify(self.signers[p].public, msg, sig)
                for p, msg, sig in items]

    def reference_certificate(self, d: int) -> bytes:
        return ref_bls.expected_certificate(self.poly, self.digests[d])
