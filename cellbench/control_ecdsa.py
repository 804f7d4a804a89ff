"""python3 -m cellbench.control_ecdsa --workload <name> --seed <n> --seconds <s>

The control of the `served_apollo` driver's own comparisons, beside
`cellbench/control.py` and `cellbench/control_bls.py` (whose plants hold
for this driver too: it is `served_bls` underneath). It breaks one
stated guarantee under a whole run, and the run has to come out not
correct by the comparison named.

served_apollo (apollo_n31):
  control.device_accepts_all  the device tier's verdict is "accept" for
      every signature it is shown — the kernel still runs, its answer is
      thrown away: breaks "every verdict of the program's verify plane
      equals the reference's" (`verdict_mismatches`: the forged and the
      truncated item of the replayed sample are accepted). The run's
      honest traffic cannot show it; only the comparison can.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from unittest import mock


@contextlib.contextmanager
def planted():
    """The program with a device tier that accepts everything."""
    import numpy as np
    from tpubft.ops import ecdsa
    real = ecdsa.rlc_verify_batch

    def accept_all(curve_name, items):
        real(curve_name, items)
        return np.ones(len(items), bool)

    with mock.patch.object(ecdsa, "rlc_verify_batch", accept_all):
        yield


def main(argv=None) -> int:
    from cellbench import harness, run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    with planted():
        result = run.run_cell(harness.Cell(args.workload), args.seed,
                              args.seconds, False)
    failing = {k: v for k, v in result["compared"].items()
               if v["value"] > v["limit"]}
    print(json.dumps({"plant": "control.device_accepts_all",
                      "seed": args.seed, "correct": result["correct"],
                      "failing": failing,
                      "compared": result["compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
