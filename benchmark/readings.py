#!/usr/bin/env python3
"""By hand, on the chip: the readings a cell's limits are set from, in ONE
process (set-up is most of a run).  Never run by the benchmark.

    python benchmark/readings.py --workload <cell> --seed <n> \
        [--hold 0,1,2,...] [--windows 10,20,30] [--repeat 3]

``--hold``: for each slice index k, go back to the marked state (a mix with
laps) or go on (one without), run k+1 slices holding the last, and print
every compared number twice: the program's against the reference (the
LOWER reading) and the reference in bfloat16 put in the program's place
(the control, the UPPER reading).  ``--windows``: timed windows of these
lengths, ``--repeat`` times each, through the harness's own loop: how the
rates spread with the window's length within one process.  Prints no
result line.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import run  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--hold", default="")
    ap.add_argument("--windows", default="")
    ap.add_argument("--repeat", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true",
                    help="sandbox: tiny levels, whatever backend there is")
    args = ap.parse_args()
    sys.path.insert(0, run.ROOT)
    bench, cell, config, traffic, peaks = run.load_cell(args.workload)
    import ramses_tpu  # noqa: F401
    import jax
    devices = jax.devices() if args.rehearse \
        else run.look_for_chip(cell, peaks)
    from benchmark.harness import check, window

    t0 = time.perf_counter()
    entry, phases = run.set_up(config, traffic, args.seed, args.rehearse)
    run.say(f"[setup] {time.perf_counter() - t0:.2f} s "
            + json.dumps({k: round(v, 3) for k, v in phases.items()}))

    for seconds in [float(x) for x in args.windows.split(",") if x]:
        for rep in range(args.repeat):
            w = window.timed_window(entry, traffic, seconds, None, 0.5)
            c = w["counts"]
            stats = devices[0].memory_stats() or {}
            run.say(f"[window {seconds:g} s #{rep}] wall_s={w['wall_s']:.4f} "
                    f"steps={c.steps_done} laps_off={c.laps_off} "
                    f"cell_updates_per_s={c.cell_updates / w['wall_s']:.6e} "
                    f"sim_time_per_s={c.sim_time / w['wall_s']:.6e} "
                    f"window_compile_s={w['window_compile_s']:.3f} "
                    f"peak={stats.get('peak_bytes_in_use')}")
    for k in [int(x) for x in args.hold.split(",") if x]:
        if hasattr(entry, "rewind"):
            entry.rewind()
        for i in range(k + 1):
            entry.run_slice(hold=(i == k))
        entry.sync()
        snap = entry.snapshot()
        if hasattr(entry, "shape_report"):
            run.say(f"[hold {k}] nstep_out={snap.get('nstep_out')} shapes="
                    + json.dumps(entry.shape_report()))
        for who in (None, "bfloat16"):
            numbers, ok = check.compare(config, snap, who)
            run.say(f"[hold {k}] {who or 'program'} correct={ok} "
                    + json.dumps({n: v for n, (v, _) in numbers.items()}))
        del snap

    if hasattr(entry, "held_device_bytes"):
        run.say(f"[yardstick] held_device_bytes={entry.held_device_bytes()}")


if __name__ == "__main__":
    main()
