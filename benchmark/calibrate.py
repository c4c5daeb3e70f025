"""The readings behind each limit of ``correct``: the program's numbers
over many seeds (the lower readings) and the control's (the upper
readings), in one process on the chip.

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed it runs the cell as ``run.py`` does (the timed path at the
cell's own size and load) and prints the numbers compared.  Then it puts
the control in the program's place: the plain reference computed with
the mean, not the median, of the famous witnesses' times (ts_rule 1 in
reference/consensus.cpp), which breaks the consensus-timestamp guarantee
the configuration states.  The control's decisions, on the same DAG,
are compared with the reference's by the same comparison.  The
benchmark's own runs never run this."""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def control_readings(internals: dict) -> dict:
    """The control's numbers: the reference with mean timestamps in the
    program's place, compared with the reference as the run compares."""
    from benchmark.reference import hashgraph, native

    dag, n, ref = internals["dag"], internals["n"], internals["reference"]
    _, ctl = native.consensus(dag, n, ts_rule=1)
    return {"events_differing":
            hashgraph.events_differing(ref, ctl, len(dag["sp"]))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from benchmark import run

    lower: dict = {}
    upper: dict = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(args.workload, seed, args.seconds, False)
        prog = {k: c["value"] for k, c in res["checks"].items()}
        ctl = control_readings(res["_internals"])
        print("READING", json.dumps({"seed": seed, "program": prog,
                                     "control": ctl,
                                     "attempted": res["attempted"],
                                     "metrics": res["metrics"]}), flush=True)
        for k, v in prog.items():
            lower[k] = max(lower.get(k, v), v)
        for k, v in ctl.items():
            upper[k] = min(upper.get(k, v), v)
    print("SUMMARY", json.dumps({"lower": lower, "upper": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
