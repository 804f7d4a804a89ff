"""Plain reference for `skvbc_n4`: SimpleKVBC's semantics as a
dictionary and a counter, nothing of the program imported. A write with
an empty read-set always succeeds and becomes one block; a read of the
latest version returns the last value written to each key."""
from __future__ import annotations


class Ledger:
    def __init__(self) -> None:
        self.state = {}
        self.blocks = 0

    def write(self, pairs) -> int:
        """Apply one write transaction; returns its block's number."""
        for key, value in pairs:
            self.state[key] = value
        self.blocks += 1
        return self.blocks

    def read(self, keys) -> dict:
        return {k: self.state[k] for k in keys if k in self.state}
