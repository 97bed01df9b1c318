#!/usr/bin/env python3
"""Read the numbers ``correct`` compares, for the program and for the
control, on several seeds in one process.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 \
        --seconds <s>

For each seed it runs the cell as ``run.py`` does (set-up, a window of
``--seconds``), then compares the sampled rows twice: the program's rows
against the plain reference (the lower reading: sound runs), and the
control's rows against it. The control is the reference with the one
guarantee that the configuration names under ``control`` broken, put in
the program's place (the upper reading). One JSON line per seed. The
benchmark's own runs never run this; the limits in ``check.py`` were set
from its readings (PERF.md section 2).
"""
import argparse
import json
import sys

import run


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    _, cell, cfg, traffic = run.spec.load(args.workload)
    try:
        jax = run.import_system()
        devices = run.chips(jax, int(cell["chips"]))
    except run.CannotRun as exc:
        print(f"control.py: {exc}", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        m = run.measure(jax, cell, cfg, traffic, seed, args.seconds, False,
                        devices)
        prog = run.decide(cfg, traffic, m["units"])
        ctrl = run.decide(cfg, traffic, m["units"], control=cfg["control"])
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "units": len(m["units"]), "rows": prog["rows"],
                          "program": prog["checks"],
                          "program_correct": prog["correct"],
                          "control": ctrl["checks"],
                          "control_correct": ctrl["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
