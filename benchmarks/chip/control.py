#!/usr/bin/env python3
"""The control, and the faults, put in the program's place and compared
with the float32 reference exactly as a run compares the program.  The
control is the reference one precision step below the configuration's
bfloat16 (every matmul operand rounded to float8 e4m3, ``fp8``); the
fault ``half`` leaves half of each batch out.  Their gaps set the upper
reading of each limit; the benchmark's own runs never run them.

    python3 benchmarks/chip/control.py --workload danube-ps.solo \\
        --seeds 11 12 13

It follows the schedule the program keeps: on the parameter server every
worker's first rounds in worker order on every shard, on the SPMD
trainer the delay its controller picks with no collective to wait for.
Prints one JSON line per seed.
"""

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT / "src"), str(HERE.parent)]


#: What can be put in the program's place: the control, and the
#: half-batch fault.
VARIANTS = {"fp8": {"prec": "fp8"}, "half": {"half": True}}


def control_gaps(cfg, traffic, seed, variants=tuple(VARIANTS)):
    """{variant: {name: gap}} of each variant against the float32
    reference on one seed, measured as a run measures the program."""
    from chip import cells
    engine = cells.ENGINES[cfg["program"]["engine"]]
    cell = engine(cfg, traffic, seed)
    if engine is cells.PSCell:
        order = list(range(cell.workers))

        def replay(**kw):
            losses, grad1, change = cells.ps_replay(
                cell, lambda k, leaf: [(0, None, order)], **kw)
            return ([x for w in sorted(losses) for x in losses[w]],
                    grad1, change)
    else:
        s = cfg["sync"]
        d = min(max(int(s["s_lower"]), 1), int(s["s_upper"]))

        def replay(**kw):
            return cells.spmd_replay(cell, [d] * cells.CHECK_STEPS, d,
                                     **kw)

    l32, g32, c32 = replay()
    out = {}
    for v in variants:
        lx, gx, cx = replay(**VARIANTS[v])
        out[v] = cells.compare(lx, l32, (gx, g32), (cx, c32))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+", default=sorted(VARIANTS),
                    choices=sorted(VARIANTS))
    args = ap.parse_args(argv)
    from chip import run
    _, work, cfg, traffic, _ = run.load_cell(ROOT, args.workload)
    run.require_chips(int(work["chips"]))
    run.use_compile_cache()
    for seed in args.seeds:
        t0 = time.monotonic()
        gaps = control_gaps(cfg, traffic, seed, args.variants)
        print(json.dumps({"workload": work["name"], "seed": seed,
                          "gaps": gaps,
                          "seconds": time.monotonic() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
