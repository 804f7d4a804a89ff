"""python3 -m cellbench.control --workload <name> --plant <fault> --seed <n> --seconds <s>

The controls and the planted faults that `correct` has been shown to
fail (PERF.md §2 gives the readings). A plant breaks the timed path
underneath a whole run — the program's own classes, patched for the
length of the run — and the run's comparisons have to come out not
correct. The benchmark's own runs never plant anything; this entry is
for the on-chip readings of a control, and tests/cellbench drives the
same plants at a tiny size on XLA-CPU.

served (skvbc_n4):
  control.replica_skips_writes  one replica acknowledges every fifth
      write without applying it: breaks "all four ledgers end
      byte-identical"
  fault.state_unchanged   every replica acknowledges writes and applies
      none (a step that returns its state unchanged)
  fault.half_batch        every replica applies every second write only
      (half of the batch left out)
  fault.answer_altered    every replica stores a value with its first
      byte flipped (an answer altered where it is produced)
flood (flood_n1000):
  control.share_dropped   the accumulator drops one honest share a
      slot: breaks "the certificate combined from any 667 honest
      shares is the threshold signature"
  fault.half_batch        the batch backend verifies the first half of
      a batch and accepts the rest unseen
  fault.answer_altered    one verdict a batch is flipped where it is
      produced
"""
from __future__ import annotations

import argparse
import json
import sys
from unittest import mock


def _served_write(mode: str):
    """A replacement for SkvbcHandler._execute_write."""
    from tpubft.apps import skvbc
    real = skvbc.SkvbcHandler._execute_write
    handlers = []                   # in order of first use: replica ids

    def skip(self):
        return skvbc.pack(skvbc.WriteReply(
            success=True, latest_block=self._bc.last_block_id))

    def write(self, msg):
        if self not in handlers:
            handlers.append(self)
        self._planted_n = getattr(self, "_planted_n", 0) + 1
        if mode == "state_unchanged":
            return skip(self)
        if mode == "half_batch" and self._planted_n % 2 == 0:
            return skip(self)
        if (mode == "replica_skips_writes" and self._planted_n % 5 == 0
                and handlers[0] is self):
            return skip(self)
        if mode == "answer_altered":
            msg.writeset = [(k, bytes([v[0] ^ 1]) + v[1:])
                            for k, v in msg.writeset]
        return real(self, msg)
    return skvbc.SkvbcHandler, "_execute_write", write


def _flood_batch(mode: str):
    """A replacement for crypto/tpu.verify_batch_mixed."""
    from tpubft.crypto import tpu
    real = tpu.verify_batch_mixed

    def batch(items):
        if mode == "half_batch":
            half = len(items) // 2
            return list(real(items[:half])) + [True] * (len(items) - half)
        out = list(real(items))
        out[len(out) // 3] = not out[len(out) // 3]
        return out
    return tpu, "verify_batch_mixed", batch


def _flood_add():
    """A replacement for BlsThresholdAccumulator.add."""
    from tpubft.crypto import systems
    real = systems.BlsThresholdAccumulator.add

    def add(self, share_id, share):
        n = real(self, share_id, share)
        if share_id in self._shares and not getattr(self, "_planted", 0):
            self._planted = 1           # the first honest share is lost
            del self._shares[share_id]
            return n - 1
        return n
    return systems.BlsThresholdAccumulator, "add", add


PLANTS = {
    "served": {
        "control.replica_skips_writes":
            lambda: _served_write("replica_skips_writes"),
        "fault.state_unchanged": lambda: _served_write("state_unchanged"),
        "fault.half_batch": lambda: _served_write("half_batch"),
        "fault.answer_altered": lambda: _served_write("answer_altered"),
    },
    "flood": {
        "control.share_dropped": _flood_add,
        "fault.half_batch": lambda: _flood_batch("half_batch"),
        "fault.answer_altered": lambda: _flood_batch("answer_altered"),
    },
}


def planted(driver: str, plant: str):
    """Context manager: the program with `plant` underneath."""
    return mock.patch.object(*PLANTS[driver][plant]())


def main(argv=None) -> int:
    from cellbench import harness, run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--plant", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload)
    with planted(cell.config["driver"], args.plant):
        result = run.run_cell(cell, args.seed, args.seconds, False)
    failing = {k: v for k, v in result["compared"].items()
               if v["value"] > v["limit"]}
    print(json.dumps({"plant": args.plant, "seed": args.seed,
                      "correct": result["correct"], "failing": failing,
                      "compared": result["compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
