#!/usr/bin/env python3
"""The benchmark's command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, the chips the cell asks for.  Finds the cell in
``BENCHMARK.json`` and everything that belongs to it BY NAME: the
configuration ``configs/<config>.json`` (+ its namelist, its entry adapter
``entries/<entry>.py`` and its plain reference ``reference/<reference>.py``),
the traffic mix ``traffic/<traffic>.json`` and, for a traced run, one reader
``layer_metrics/<metric>.py`` per per-layer metric.  Adding a cell, a mix or
a metric is adding files and one entry; no file here is edited.

Exits non-zero WITHOUT a result line when the program is absent, when JAX
finds no TPU, an unknown ``device_kind`` or too few chips.  The last line of
standard output is the result object and nothing else.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(ROOT, ".bench_out")


def say(msg):
    print(msg, flush=True)


def die(code, msg):
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name):
    """(BENCHMARK.json, the cell's entry, its configuration, its mix,
    the table of peaks), each found by the name the entry gives."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        die(2, f"no workload {name!r} in BENCHMARK.json")
    return (bench, cell,
            load_json(HERE, "configs", cell["config"] + ".json"),
            load_json(HERE, "traffic", cell["traffic"] + ".json"),
            load_json(HERE, "peaks.json"))


def metrics_of(bench, kind, cell_name):
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def base_name(metric_name):
    """``step_device_ms.host_bound`` is ``step_device_ms`` read in a cell
    whose rate is another end-to-end metric (a per-layer metric moves one):
    what follows the first dot says which, the name before it is the
    quantity, and with it the reader and the harness's own number."""
    return metric_name.split(".")[0]


def layer_reader(metric_name):
    return importlib.import_module("benchmark.layer_metrics."
                                   + base_name(metric_name))


def look_for_chip(cell, peaks):
    """The devices the cell runs on, or exit: never a fallback."""
    import jax
    dev = jax.devices()
    platform, kind = dev[0].platform, dev[0].device_kind
    if platform != "tpu":
        die(4, f"JAX found platform {platform!r}, not a TPU; the "
               f"benchmark never falls back")
    if kind not in peaks:
        die(4, f"device_kind {kind!r} is not in benchmark/peaks.json")
    if len(dev) < int(cell["chips"]):
        die(4, f"{len(dev)} chip(s) found, the cell asks for "
               f"{cell['chips']}")
    return dev[:int(cell["chips"])]


def build_params(config, traffic, seed, rehearse):
    """Namelist → params, levels shrunk for a rehearsal, blast placed from
    the seed (the rule's parameters are the mix's)."""
    from ramses_tpu.config import load_params
    from benchmark.harness import seed as seedmod
    params = load_params(os.path.join(HERE, "configs", config["namelist"]),
                         ndim=3)
    level = int(config["seed"]["level"])
    if rehearse:
        r = config["rehearse"]
        params.amr.levelmin, params.amr.levelmax = r["levelmin"], r["levelmax"]
        level = int(r["seed_level"])
    params.run.nstepmax = 0
    centre, index = seedmod.blast_centre(
        seed, level, int(config["seed"]["pitch_cells"]),
        float(params.amr.boxlen))
    seedmod.place_blast(params, centre, int(config["point_region"]))
    say(f"[seed] {seed}: blast at cell corner {index} of the 2^{level} grid "
        f"= {centre}")
    return params


def set_up(config, traffic, seed, rehearse=False):
    """The entry, built, developed and warmed: every program of the window
    compiled or loaded.  Returns (entry, seconds of each phase)."""
    from benchmark.harness import window
    t0 = time.perf_counter()
    params = build_params(config, traffic, seed, rehearse)
    entry_mod = importlib.import_module("benchmark.entries."
                                        + config["entry"])
    entry = entry_mod.Entry(config, traffic, params)
    phases = {"build_s": time.perf_counter() - t0}
    phases.update(window.warm_up(entry, traffic))
    return entry, phases


def window_and_judge(bench, cell, config, traffic, peak, entry, *,
                     seconds, seed=0, trace=False, setup_s=0.0, devices=(),
                     control=None, rehearse=False, free=True):
    """The rest of a run once the entry is warm: the timed window, the
    metrics, what ran per level, the comparison.  Returns the result
    object (``benchmark/tests`` drives this with the timed path broken)."""
    from benchmark.harness import check, trace_reduce, window
    from benchmark.harness.seed import check_fraction
    if rehearse:       # drifts read higher on a rehearsal's few cells
        config = dict(config, limits={**config["limits"],
                                      **config["rehearse"].get("limits", {})})
    trace_dir = None
    if trace:
        trace_dir = os.path.join(OUT, "trace", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    w = window.timed_window(entry, traffic, seconds, trace_dir,
                            check_fraction(seed))
    stats = [d.memory_stats() or {} for d in devices]
    peak_bytes = max((s.get("peak_bytes_in_use", 0) for s in stats),
                     default=0)
    counts = w["counts"]
    say(f"[window] wall_s={w['wall_s']:.4f} slices={counts.slices} "
        f"steps={counts.steps_done}/{counts.steps_asked} "
        f"cell_updates={counts.cell_updates} regrids={counts.regrids} "
        f"laps_off={counts.laps_off} sim_time={counts.sim_time:.6e} "
        f"window_compile_s={w['window_compile_s']:.4f} "
        f"peak_bytes_in_use={peak_bytes} "
        f"bytes_limit={stats[0].get('bytes_limit') if stats else None}")
    if w["window_compile_s"] > 0 or w["compiled"]:
        say(f"[window] COMPILED OR LOADED INSIDE THE WINDOW: "
            f"{w['window_compile_s']:.3f} s — the rates include it: "
            + " ".join(w["compiled"]))
    ends = w["slice_ends"]
    walls = sorted((b - a, i) for i, (a, b) in
                   enumerate(zip([0.0] + ends[:-1], ends)))
    say(f"[slices] wall of a slice, ms: min {1e3 * walls[0][0]:.2f} "
        f"median {1e3 * walls[len(walls) // 2][0]:.2f}; the longest: "
        + ", ".join(f"#{i} {1e3 * d:.2f}" for d, i in walls[:-4:-1]))
    if hasattr(entry, "held_device_bytes"):
        say(f"[yardstick] held_device_bytes={entry.held_device_bytes()} "
            f"(device arrays the lap mark keeps alive, inside "
            f"peak_bytes_in_use)")
    if hasattr(entry, "tend") and entry.sim_time() >= entry.tend():
        die(5, "the run reached the namelist's end time inside the window: "
               "masked no-op steps would count as work")
    if hasattr(entry, "shape_report"):
        say(f"[shapes] {json.dumps(entry.shape_report())}")

    # ------------------------------------------------ what ran, per level
    for label, text, on_kernel in entry.formulations(count_calls=trace):
        say(f"[formulation] {label}: {text}"
            + ("" if on_kernel or rehearse else
               "   <<< OFF ITS KERNEL — a finding, not a failure of the run"))

    # ------------------------------------------------------- the metrics
    failed = counts.steps_asked - counts.steps_done + counts.laps_off
    if counts.laps_off:
        say(f"[window] {counts.laps_off} STEP(S) DID NOT REPEAT THE FIRST "
            f"LAP'S: the window did not do the same work lap after lap")
    if not entry.finite():
        failed = counts.steps_asked
        say("[window] NON-FINITE state after the window")
    metrics = {}
    reduced = None
    if trace:
        xplane = trace_reduce.find_xplane(trace_dir)
        try:
            reduced = trace_reduce.reduce_trace(xplane)
        except ValueError as e:
            if not rehearse:       # a traced run with no device op is void
                raise
            say(f"[trace] rehearsal backend gives nothing to reduce: {e}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"config": config, "traffic": traffic, "peak": peak,
               "window_compile_s": w["window_compile_s"],
               "cell": cell["name"]}
        for m in metrics_of(bench, "per_layer", cell["name"]) \
                if reduced is not None else ():
            val = layer_reader(m["name"]).read(
                reduced, reduced["spans"], w["traced"].as_dict(), ctx)
            if val is not None:
                metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    else:
        values = {
            "setup_s": setup_s,
            "cell_updates_per_s": counts.cell_updates / w["wall_s"],
            "sim_time_per_s": counts.sim_time / w["wall_s"],
            "peak_hbm_bytes": float(peak_bytes),
        }
        for m in metrics_of(bench, "end_to_end", cell["name"]):
            metrics[m["name"]] = {"value": float(values[base_name(m["name"])]),
                                  "unit": m["unit"]}

    # ---------------------------------------------------- the comparison
    t_check = time.perf_counter()
    snap = entry.snapshot()
    if free:
        entry.free()
    compared, ok = check.compare(config, snap, control)
    del snap
    say(f"[check] reference and comparison took "
        f"{time.perf_counter() - t_check:.2f} s"
        + (f" (CONTROL: reference in {control} in the program's place)"
           if control else ""))

    result = {"correct": bool(ok and failed == 0),
              "attempted": counts.steps_asked, "failed": failed,
              "metrics": metrics,
              "device": {"memory_peak_bytes": int(peak_bytes)}}
    if reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = trace_reduce.breakdown(reduced)
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in compared.items()}
    for k, (v, lim) in compared.items():
        print(f"compared {k}: value={v:.6e} limit={lim:.6e} "
              f"{'ok' if v <= lim else 'OVER'}", file=sys.stderr)
    sys.stderr.flush()
    return result


def main(argv=None, rehearse=False):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default="",
                    help="by hand only: put the reference computed in this "
                         "dtype in the program's place in the comparison")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    bench, cell, config, traffic, peaks = load_cell(args.workload)
    try:
        import ramses_tpu  # noqa: F401  (engages the compile cache)
    except ImportError as e:
        die(3, f"the program is not in this checkout: {e}")
    import jax
    devices = jax.devices() if rehearse else look_for_chip(cell, peaks)
    platform, kind = devices[0].platform, devices[0].device_kind
    from ramses_tpu.platform import compile_cache_stats
    say(f"[device] platform={platform} kind={kind} count={len(devices)} "
        f"compile_cache={compile_cache_stats()['dir'] or '(off)'}")

    entry, phases = set_up(config, traffic, args.seed, rehearse)
    c_setup = compile_cache_stats()
    setup_s = time.perf_counter() - T_PROCESS
    say(f"[setup] setup_s={setup_s:.3f} ("
        + ", ".join(f"{k} {v:.3f}" for k, v in phases.items()) + ") "
        f"compile_timer_s={c_setup['compile_s']:.3f} "
        f"cache_hits={c_setup['hits']} cache_misses={c_setup['misses']}")

    result = window_and_judge(
        bench, cell, config, traffic, peaks.get(kind), entry,
        seconds=args.seconds, seed=args.seed, trace=bool(args.trace),
        setup_s=setup_s, devices=devices, control=args.control or None,
        rehearse=rehearse)
    result["device"] = {"platform": platform, "kind": kind,
                        "count": len(devices), **result["device"]}
    result["compared"] = result.pop("compared")      # comes last
    line = json.dumps(result)
    if rehearse:
        say("REHEARSAL (not a result; sizes and platform are not the "
            "cell's): " + line)
        return result
    say(line)
    return result


if __name__ == "__main__":
    main()
