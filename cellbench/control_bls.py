"""python3 -m cellbench.control_bls --workload <name> --plant <fault> --seed <n> --seconds <s>

The controls of the `served_bls` driver's own comparisons, beside
`cellbench/control.py` (whose plants on the ledger path hold for this
driver too: it is `served` underneath). Each breaks the timed path
under a whole run, and the run has to come out not correct by the
comparison named.

served_bls (skvbc_n7_bls):
  control.shares_swapped  a collector combines two shares under each
      other's signer ids and the combined-certificate check accepts
      whatever it is shown, so the cluster commits on signatures that
      are not the threshold signature: breaks "every certificate
      compared equals the reference's" (`certificate_mismatches`, and
      `certificates_unverified` with it: a BLS signature that verifies
      is unique)
  fault.verify_rejects    the verifier's single-certificate pairing
      check, which the fused path never calls, rejects everything: the
      run is sound and `certificates_unverified` alone says so
  fault.path_unrecorded   the flight recorder loses which path
      committed a slot (`slots_on_no_path`)
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from unittest import mock


def _shares_swapped():
    from tpubft.crypto import systems
    cls = systems.BlsThresholdVerifier
    real = cls._decode_job_shares

    def decode(self, shares):
        pts = real(self, shares)
        if len(pts) >= 2:
            a, b = sorted(pts)[:2]
            pts[a], pts[b] = pts[b], pts[a]
        return pts

    def accept_all(self, items):
        return [True] * len(items)
    return [(cls, "_decode_job_shares", decode),
            (cls, "verify_batch_certs", accept_all)]


def _verify_rejects():
    from tpubft.crypto import systems
    return [(systems.BlsThresholdVerifier, "verify",
             lambda self, data, sig: False)]


def _path_unrecorded():
    from tpubft.utils import flight
    real = flight.SlotTracker.stamp.__func__

    def stamp(cls, slot, code, arg, t_ns):
        real(cls, slot, code, arg, t_ns)
        slot.pop("path", None)
    return [(flight.SlotTracker, "stamp", classmethod(stamp))]


PLANTS = {
    "control.shares_swapped": _shares_swapped,
    "fault.verify_rejects": _verify_rejects,
    "fault.path_unrecorded": _path_unrecorded,
}


@contextlib.contextmanager
def planted(plant: str):
    """The program with `plant` underneath."""
    with contextlib.ExitStack() as stack:
        for target, name, new in PLANTS[plant]():
            stack.enter_context(mock.patch.object(target, name, new))
        yield


def main(argv=None) -> int:
    from cellbench import harness, run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--plant", required=True, choices=sorted(PLANTS))
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    with planted(args.plant):
        result = run.run_cell(harness.Cell(args.workload), args.seed,
                              args.seconds, False)
    failing = {k: v for k, v in result["compared"].items()
               if v["value"] > v["limit"]}
    print(json.dumps({"plant": args.plant, "seed": args.seed,
                      "correct": result["correct"], "failing": failing,
                      "compared": result["compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
