"""Abstract key-value DB interface.

Rebuild of the reference's `concord::storage::IDBClient`
(/root/reference/storage/include/storage/db_interface.h:55): get / put /
del / multiGet / range iteration / atomic write batches, plus RocksDB-style
column families ("families" here). Families are encoded as a
length-prefixed key prefix so every backend gets them for free and range
scans stay contiguous per family.
"""
from __future__ import annotations

import abc
import struct
from typing import (Dict, Iterable, Iterator, List, Optional, Sequence, Set,
                    Tuple, Union)

DEFAULT_FAMILY = b"default"


class StorageError(Exception):
    pass


def family_prefix(family: bytes) -> bytes:
    """What every physical key of `family`, and of no other, starts
    with. Family names are <=255 bytes, so the 1-byte length prefix
    keeps families disjoint and contiguous."""
    if len(family) > 255:
        raise StorageError("family name too long")
    return bytes([len(family)]) + family


def fkey(family: bytes, key: bytes) -> bytes:
    """Compose the physical key."""
    return family_prefix(family) + key


def split_fkey(physical: bytes) -> Tuple[bytes, bytes]:
    n = physical[0]
    return physical[1:1 + n], physical[1 + n:]


def family_upper_bound(family: bytes) -> Optional[bytes]:
    """Smallest physical key strictly greater than every key in `family`
    (None = unbounded, i.e. family is the last possible)."""
    b = bytearray(family_prefix(family))
    for i in reversed(range(len(b))):
        if b[i] != 0xFF:
            b[i] += 1
            return bytes(b[:i + 1])
    return None


def encode_rows(rows: Sequence[Tuple[bytes, Optional[bytes]]]) -> bytes:
    """Canonical wire encoding shared with the native engine (kvlog.cpp):
    repeat{ u8 op(1=put,2=del) | u32le klen | key | [u32le vlen | val] }"""
    out = bytearray()
    for k, v in rows:
        if v is None:
            out += b"\x02" + len(k).to_bytes(4, "little") + k
        else:
            out += (b"\x01" + len(k).to_bytes(4, "little") + k
                    + len(v).to_bytes(4, "little") + v)
    return bytes(out)


class EncodedRows:
    """A run of rows born in the wire encoding (the native merkle walk's,
    tpubft/native/smtwalk.cpp): `payload` is `encode_rows` of them,
    `index` holds four u32le a row — key start, key end, value start,
    value end, offsets into `payload`, 0 and 0 for a delete — and
    `families` the family prefixes their keys carry."""

    __slots__ = ("payload", "index", "families")
    _ROW = struct.Struct("<4I")

    def __init__(self, payload: bytes, index: bytes,
                 families: Sequence[bytes]) -> None:
        self.payload = payload
        self.index = index
        self.families = families

    def __len__(self) -> int:
        return len(self.index) // self._ROW.size

    def rows(self) -> List[Tuple[bytes, Optional[bytes]]]:
        """(physical key, value — None deletes) per row, in order. A
        value never starts at offset 0: the row's key comes first."""
        p = self.payload
        return [(p[k0:k1], p[v0:v1] if v0 else None)
                for k0, k1, v0, v1 in self._ROW.iter_unpack(self.index)]


class WriteBatch:
    """Ordered, atomic batch of put/delete ops across families
    (reference: ITransaction / rocksdb::WriteBatch). Rows come one at a
    time (`put`, `delete`), as a list (`extend`) or already encoded
    (`extend_encoded`); the batch keeps them in the order they came and
    never re-encodes what arrived encoded."""

    def __init__(self) -> None:
        # in order: lists of (physical_key, value-or-None), EncodedRows
        self._parts: List[Union[List[Tuple[bytes, Optional[bytes]]],
                                EncodedRows]] = []
        # family prefixes (`family_prefix`) of every row so far
        self.families: Set[bytes] = set()

    def _tail(self) -> List[Tuple[bytes, Optional[bytes]]]:
        """The list new rows join: the last part, if it is a list."""
        parts = self._parts
        if parts and type(parts[-1]) is list:
            return parts[-1]
        parts.append([])
        return parts[-1]

    def put(self, key: bytes, value: bytes,
            family: bytes = DEFAULT_FAMILY) -> "WriteBatch":
        prefix = family_prefix(family)
        self.families.add(prefix)
        self._tail().append((prefix + key, bytes(value)))
        return self

    def delete(self, key: bytes,
               family: bytes = DEFAULT_FAMILY) -> "WriteBatch":
        prefix = family_prefix(family)
        self.families.add(prefix)
        self._tail().append((prefix + key, None))
        return self

    def extend(self, ops: Sequence[Tuple[bytes, Optional[bytes]]],
               families: Optional[Iterable[bytes]] = None) -> "WriteBatch":
        """Append `ops` in order: (physical key, value — None deletes),
        the key already composed with `fkey`. For a caller that stages
        many rows of a few families at once, and says which (their
        `family_prefix`es); without `families` they are read off the
        keys."""
        self.families.update(families if families is not None
                             else {k[:1 + k[0]] for k, _ in ops})
        self._tail().extend(ops)
        return self

    def extend_encoded(self, rows: EncodedRows) -> "WriteBatch":
        """Append rows that are already in the wire encoding."""
        self.families.update(rows.families)
        self._parts.append(rows)
        return self

    def __len__(self) -> int:
        return sum(map(len, self._parts))

    @property
    def ops(self) -> List[Tuple[bytes, Optional[bytes]]]:
        """Every row as (physical key, value — None deletes), in order:
        encoded runs are decoded on demand, so this is for a reader that
        needs the rows (a store without the wire format, a test) — ask
        `len()` for whether there are any, `families` for whose."""
        parts = self._parts
        if len(parts) == 1 and type(parts[0]) is list:
            return parts[0]
        out: List[Tuple[bytes, Optional[bytes]]] = []
        for part in parts:
            out.extend(part.rows() if isinstance(part, EncodedRows)
                       else part)
        return out

    def encode(self) -> bytes:
        """The batch in the wire encoding (`encode_rows`)."""
        return b"".join(part.payload if isinstance(part, EncodedRows)
                        else encode_rows(part) for part in self._parts)


class IDBClient(abc.ABC):
    """Abstract ordered KV store (db_interface.h:55)."""

    # True where `get` never releases the interpreter lock (NativeDB's
    # engine calls; memory stores run no C call at all and say False):
    # what the `kvbc` counter `smt_engine_reads_lock_kept` reads
    point_reads_keep_lock = False

    @abc.abstractmethod
    def get(self, key: bytes,
            family: bytes = DEFAULT_FAMILY) -> Optional[bytes]: ...

    @abc.abstractmethod
    def write(self, batch: WriteBatch) -> None: ...

    @abc.abstractmethod
    def range_iter(self, family: bytes = DEFAULT_FAMILY,
                   start: Optional[bytes] = None,
                   end: Optional[bytes] = None
                   ) -> Iterator[Tuple[bytes, bytes]]:
        """Iterate (key, value) for start <= key < end within a family."""

    @abc.abstractmethod
    def close(self) -> None: ...

    def sync(self) -> None:
        """Force everything written so far onto stable storage (the
        group-commit fsync seam — one call durably lands every batch
        applied since the previous sync). Backends without a durability
        boundary (memory stores) are a no-op; NativeDB overrides with a
        real fsync. Callers outside tpubft/durability/ are lint-banned
        (tools/tpulint fsync-seam pass): amortizing this call is the
        durability pipeline's whole job, and a stray per-write sync
        silently reintroduces the per-run disk tax."""

    def write_group(self, batches: Sequence[WriteBatch]) -> None:
        """Apply several batches as one group, in order (the durability
        pipeline's group-concatenation seam). The default preserves
        per-batch atomicity only; NativeDB overrides by concatenating
        the group into ONE engine record — one apply, one CRC, and (in
        sync_writes mode) one fsync for the whole group."""
        for b in batches:
            if len(b):
                self.write(b)

    def scan_all(self) -> "Iterator[Tuple[bytes, bytes, bytes]]":
        """Iterate EVERY (family, key, value) in the store — the
        whole-state snapshot walk (reference: RocksDB checkpoint /
        state-snapshot streaming). Backends with a physical-order scan
        override this."""
        raise NotImplementedError

    # ---- conveniences built on the primitives ----
    def put(self, key: bytes, value: bytes,
            family: bytes = DEFAULT_FAMILY) -> None:
        self.write(WriteBatch().put(key, value, family))

    def delete(self, key: bytes, family: bytes = DEFAULT_FAMILY) -> None:
        self.write(WriteBatch().delete(key, family))

    def has(self, key: bytes, family: bytes = DEFAULT_FAMILY) -> bool:
        return self.get(key, family) is not None

    def multi_get(self, keys: Sequence[bytes],
                  family: bytes = DEFAULT_FAMILY) -> List[Optional[bytes]]:
        return [self.get(k, family) for k in keys]

    def last_in_range(self, family: bytes = DEFAULT_FAMILY,
                      start: Optional[bytes] = None,
                      end: Optional[bytes] = None
                      ) -> Optional[Tuple[bytes, bytes]]:
        out = None
        for kv in self.range_iter(family, start, end):
            out = kv
        return out

    def family_dict(self, family: bytes = DEFAULT_FAMILY
                    ) -> Dict[bytes, bytes]:
        return dict(self.range_iter(family))
