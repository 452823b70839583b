"""Readings that set a cell's correctness limit, on the chip.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 51

For each seed, in one process: a run of the cell as ``run.py`` makes it
(its windows at the cell's own load), the widest relative error of its
served logits against the float32 reference and the widest gap of a
served token below the reference's best, and the control's: the
reference with every matmul's inputs rounded through float8_e4m3fn, put
in the program's place and read at the same positions. Each is judged
by ``run.result_line``, as a run's ``correct`` is: the program has to
come out correct and the control not. The benchmark's own runs never
run the control. The last line of the output is a JSON object with the
readings.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import spec as bspec  # noqa: E402


def control_result(res: dict) -> dict:
    """The run's result with the control in the program's place: its
    logits are the ones compared, its tokens the served ones."""
    return dict(res, logit_err=res["control_err"],
                logit_gap=res["control_gap"], token_mismatch=0,
                wrong_requests=res["control_wrong_requests"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import driver
    import run
    spec = bspec.benchmark()
    cell = bspec.workload(spec, args.workload)
    out = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        res = driver.run(cell, seed, args.seconds, False, t0=t,
                         report=lambda m: print(m, file=sys.stderr,
                                                flush=True),
                         control=True)
        prog = run.result_line(spec, cell, res, False)
        ctl = run.result_line(spec, cell, control_result(res), False)
        row = {"seed": seed, "program_err": res["logit_err"],
               "program_err_q": res["logit_err_q"],
               "program_correct": prog["correct"],
               "control_err": res["control_err"],
               "control_err_q": res["control_err_q"],
               "control_correct": ctl["correct"],
               "token_mismatch": res["token_mismatch"],
               "program_gap": res["logit_gap"],
               "control_gap": res["control_gap"],
               "checked_tokens": res["checked_tokens"],
               "checked_requests": res["checked_requests"],
               "followed": res["follow"],
               "metrics": prog["metrics"]}
        print(json.dumps(row), flush=True)
        out.append(row)
    print(json.dumps({"workload": args.workload, "runs": out,
                      "program_err_max": max(r["program_err"] for r in out),
                      "control_err_min": min(r["control_err"] for r in out),
                      "control_correct_any": any(r["control_correct"]
                                                 for r in out)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
