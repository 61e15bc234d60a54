"""The control of the comparison that decides ``correct``, on the chip.

    python3 benchmarks/serving/calibrate.py --workload <cell> \
        --seeds 1,2,3 --seconds <s>

For each seed, in one process: one run of the cell as ``run.py`` makes
it (set-up, the window at the cell's own load), then the reference over
the sampled requests, and the control: the same reference with its
products in int8, one step below the bfloat16 the configuration states,
put in the program's place.  At every served position the control's
first choice is read against the float32 reference, as the served token
is.
One JSON line per seed gives both sets of numbers: the program's set the
lower reading of each limit, the control's the upper one, and whether
each comes out correct against the cell's committed limits
(``limits/<cell>.json``) by the harness's own ``judge``: the control has
to come out not correct.  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import time

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402  (puts the benchmark and src/ on sys.path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import harness
    cell = harness.load_cell(args.workload)
    run.check_chips(cell.chips)
    run.use_compile_cache()
    for seed in [int(s) for s in args.seeds.split(",")]:
        res = harness.run_cell(cell, seed, args.seconds, False,
                               time.perf_counter(), control=True)
        cmp = res.get("comparison")
        control_correct = None
        if cmp is not None:
            checks = harness.judge(cell.limits, cmp["control"])
            control_correct = bool(checks) and all(
                c["ok"] for c in checks.values())
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "control_correct": control_correct,
                          "comparison": cmp,
                          "metrics": res["metrics"],
                          "memory_peak_bytes":
                              res["device"]["memory_peak_bytes"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
