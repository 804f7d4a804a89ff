"""Leaves the merkle walks found already stored, as a share of the
leaves they changed, over the window: 100 × the window's delta of the
process-wide `kvbc` counter `smt_keys_overwritten` over that of
`smt_keys_updated` (`tpubft/kvbc/sparse_merkle.py`, every replica's
ledger), as the driver snapshotted them at the window's open and
close. A stored leaf reads all 256 of its siblings; a fresh key reads
about 9 + log2(keys). None on a program or driver without the counter,
or with no leaf changed in the window."""


def read(ctx):
    before = ctx["before"].get("kvbc")
    after = ctx["after"].get("kvbc")
    if not before or not after or "smt_keys_overwritten" not in after:
        return None
    keys = after["smt_keys_updated"] - before.get("smt_keys_updated", 0)
    if keys <= 0:
        return None
    return 100.0 * (after["smt_keys_overwritten"]
                    - before.get("smt_keys_overwritten", 0)) / keys
