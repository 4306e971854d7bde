"""Readings that set a cell's limits: the program's compared numbers and
the control's, over several seeds, in one process.

    python3 -m bench.control --workload <cell> --seeds 1,2,3 --seconds 10

For each seed it runs the cell as `bench.run` does, at the cell's own
sizes and load for a short window, as a control run: the run is judged
by the reference computed one precision down in the program's place
(fp8 activations for the served model, one significand bit less for the
equalizer), and has to come out as not correct.  It prints one JSON line
per seed: `correct` and `checks` of that judgement, and the program's
own readings of the same numbers under `program_checks`.  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

from bench import run, spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)

    from repro.launch import runtime

    runtime.require_tpu(allow_cpu_if_requested=False)
    run.configure_caches()
    for seed in (int(s) for s in args.seeds.split(",")):
        result, _ = run.execute(cell, seed, args.seconds, False,
                                control=True)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": result["correct"],
                          "program_checks": result["program_checks"],
                          "checks": result["checks"],
                          "notes": result["notes"],
                          "metrics": result["metrics"],
                          "device": result["device"]}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
