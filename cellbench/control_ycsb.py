"""python3 -m cellbench.control_ycsb --plant <fault> --seed <n> --seconds <s>

The controls of `ycsb_n4.ycsb_a_c128`: a plant breaks one guarantee of
the configuration on every replica alike — the program's own
`SkvbcHandler`, patched for the length of the run, so that the replicas
still agree with each other and f+1 (or 2f+1) matching replies still
come back — and the run's comparisons have to come out not correct.
The benchmark's own runs never plant anything; tests/cellbench drives
the same plants at a tiny size on XLA-CPU.

  stale_reads       every replica answers a read-only request from the
      state two blocks old: breaks "every read returns the value at
      some block between the last update acknowledged before it was
      sent and the last update sent before it returned"
  lost_overwrites   every replica acknowledges every fifth overwrite of
      a stored key without applying it: breaks "the final state equals
      the acknowledged updates applied in the order of their blocks"
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import json
import sys
from unittest import mock

WORKLOAD = "ycsb_n4.ycsb_a_c128"
STALE_BLOCKS = 2
LOSE_EVERY = 5


@contextlib.contextmanager
def planted(plant: str):
    """The program with `plant` underneath."""
    from tpubft.apps import skvbc
    cls = skvbc.SkvbcHandler
    real_write = cls._execute_write

    def recent(self):
        if not hasattr(self, "_planted_recent"):
            self._planted_recent = collections.deque(maxlen=STALE_BLOCKS)
            self._planted_n = 0
        return self._planted_recent

    def write(self, msg):
        """Keeps, for the last blocks, the values their writes replaced;
        drops every fifth overwrite when asked to."""
        old = recent(self)
        before = [(k, self._read_at(k, skvbc.READ_LATEST))
                  for k, _v in msg.writeset]
        if plant == "lost_overwrites" and any(v is not None
                                              for _k, v in before):
            self._planted_n += 1
            if self._planted_n % LOSE_EVERY == 0:
                return skvbc.pack(skvbc.WriteReply(
                    success=True, latest_block=self._bc.last_block_id))
        out = real_write(self, msg)
        old.append(before)
        return out

    def read(self, client_id, request):
        """A read-only request answered as of `STALE_BLOCKS` blocks
        ago: each key written since, its value before that write."""
        msg = skvbc.unpack(request)
        if not isinstance(msg, skvbc.ReadRequest):
            return real_read(self, client_id, request)
        with self._lock:
            then = {}
            for before in reversed(recent(self)):    # oldest wins
                then.update(before)
            reads = []
            for k in msg.keys:
                v = then[k] if k in then else self._read_at(
                    k, msg.read_version)
                if v is not None:
                    reads.append((k, v))
            return skvbc.pack(skvbc.ReadReply(reads=reads))

    real_read = cls.read
    with mock.patch.object(cls, "_execute_write", write):
        if plant == "stale_reads":
            with mock.patch.object(cls, "read", read):
                yield
        elif plant == "lost_overwrites":
            yield
        else:
            raise SystemExit(f"no plant {plant!r}")


def main(argv=None) -> int:
    from cellbench import harness, run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--plant", required=True,
                    choices=("stale_reads", "lost_overwrites"))
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    with planted(args.plant):
        result = run.run_cell(harness.Cell(WORKLOAD), args.seed,
                              args.seconds, False)
    failing = {k: v for k, v in result["compared"].items()
               if v["value"] > v["limit"]}
    print(json.dumps({"plant": args.plant, "seed": args.seed,
                      "correct": result["correct"], "failing": failing,
                      "compared": result["compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
