"""The control: the program's own lower-precision path (bfloat16 stored
values in place of the configuration's float32), as the traffic file's
``control`` names it, switched on under the cell's traffic.  Its compared numbers have to fail the cell's limits.

Under pytest this runs on the CPU at a small size.  On the chip, at the
cell's own size, it prints the program's and the control's readings for
each seed, in one process:

    python chipbench/tests/test_control.py --workload samg.spmvm \
        --seeds 11 12 13 --seconds 5
"""
import argparse
import json
import pathlib
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench import run as RUN  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = {w["name"]: w for w in BENCH["workloads"]}


def control_overrides(workload):
    """What the control switches on: the traffic file's ``control``."""
    traffic = ROOT / "chipbench" / "traffic" / f"{CELLS[workload]['traffic']}.json"
    return json.loads(traffic.read_text())["control"]


def readings(workload, seed, seconds, *, control, **kw):
    over = control_overrides(workload) if control else None
    line = RUN.run_cell(workload, seed, seconds, False,
                        t_start=time.perf_counter(), traffic_overrides=over,
                        **kw)
    return line["correct"], {k: v["value"] for k, v in line["checks"].items()}


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_control_fails_the_limits(workload):
    small = dict(require_chip=False, scale=0.002)
    ok, sound = readings(workload, 2**31 + 5, 1.0, control=False, **small)
    bad, ctrl = readings(workload, 2**31 + 5, 1.0, control=True, **small)
    assert ok is True
    assert bad is False, ctrl


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--program", action="store_true",
                    help="read the program's own path on the same seeds too")
    args = ap.parse_args()
    sides = ((False, True) if args.program else (True,))
    for seed in args.seeds:
        for control in sides:
            try:
                ok, nums = readings(args.workload, seed, args.seconds,
                                    control=control)
            except Exception as e:              # a control that crashes fails
                ok, nums = False, {"error": f"{type(e).__name__}: {e}"}
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "control": control, "correct": ok,
                              "checks": nums}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
