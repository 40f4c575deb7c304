"""Multi-chip dryrun with SPMD partitioner-health gating.

Runs ``__graft_entry__.dryrun_multichip(n)`` in a subprocess (CPU
host-device mesh), captures stderr, and counts XLA's "Involuntary full
rematerialization" SPMD warnings — the signature of a global-view op
the partitioner could only reshard by replicating the full tensor
(MULTICHIP_r05 showed the complete-level dense sweep doing exactly
that every coarse step).  Writes ``MULTICHIP_local.json`` with the
same shape as the driver's ``MULTICHIP_*.json`` plus a top-level
``remat_warnings`` count, and exits nonzero when the count is > 0 so
CI fails loudly on a partitioner regression.

Usage::

Also mirrors the result into a telemetry JSONL event log (run-header +
``dryrun`` event + one ``xla_warning`` event per captured remat line)
next to ``--out`` so ``tools/telemetry_report.py`` renders dryruns and
runs from the same schema.

Usage::

    python tools/multichip.py [--devices N] [--out PATH]
                              [--telemetry PATH.jsonl]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REMAT_MARK = "Involuntary full rematerialization"
TAIL_BYTES = 8000


def run_dryrun(n_devices: int, repo: str):
    """One subprocess dryrun; returns (result record, raw stderr)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("XLA_FLAGS", "")
    code = (f"import __graft_entry__ as g; "
            f"g.dryrun_multichip({n_devices})")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=repo, env=env,
        capture_output=True, text=True, timeout=1800)
    stderr = proc.stderr or ""
    tail = (proc.stdout or "")[-TAIL_BYTES:] + stderr[-TAIL_BYTES:]
    remat = stderr.count(REMAT_MARK)
    return {
        "n_devices": n_devices,
        "rc": proc.returncode,
        "ok": proc.returncode == 0 and remat == 0,
        "skipped": False,
        "remat_warnings": remat,
        "tail": tail,
    }, stderr


def run_lint(n_devices: int, repo: str):
    """Static-analysis leg: ``__graft_entry__.dryrun_lint(n)`` in a
    subprocess — the same engine and baseline as ``tools/lint.py
    --check``, on the same CPU mesh as the dryrun, so a partitioner-
    visible hazard (dropped donation, non-unique scatter-add, gather
    budget blowout) fails this gate even when it does not remat."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    code = (f"import __graft_entry__ as g; "
            f"g.dryrun_lint({n_devices})")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=repo, env=env,
        capture_output=True, text=True, timeout=1800)
    tail = ((proc.stdout or "")[-TAIL_BYTES:]
            + (proc.stderr or "")[-TAIL_BYTES:])
    return {
        "n_devices": n_devices,
        "rc": proc.returncode,
        "ok": proc.returncode == 0,
        "tail": tail,
    }


def emit_telemetry(path: str, res: dict, stderr: str, repo: str):
    """Mirror the dryrun result into a telemetry JSONL event log: a
    run-header, one ``dryrun`` event, one ``xla_warning`` event per
    rematerialization line XLA wrote to the subprocess's raw stderr
    (C++ warnings never reach Python's ``warnings`` machinery — this
    fold is how they land next to the step records CI plots)."""
    sys.path.insert(0, repo)
    from ramses_tpu.telemetry import Telemetry, TelemetrySpec
    tel = Telemetry(TelemetrySpec(path=path),
                    run_info={"driver": "multichip_dryrun",
                              "ndev": res["n_devices"]})
    for line in stderr.splitlines():
        if REMAT_MARK in line:
            tel.warn(line.strip(), source="xla:stderr")
    tel.record_event("dryrun", n_devices=res["n_devices"],
                     rc=res["rc"], ok=res["ok"],
                     remat_warnings=res["remat_warnings"])
    for line in stderr.splitlines():
        if REMAT_MARK in line:
            tel.record_event("xla_warning", msg=line.strip()[:500],
                             source="xla:stderr")
    tel.close(print_timers=False)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--out", default="MULTICHIP_local.json")
    ap.add_argument("--telemetry", default=None,
                    help="telemetry JSONL path (default: --out with a "
                         ".jsonl suffix)")
    ap.add_argument("--lint", action="store_true",
                    help="also run the static-analysis leg "
                         "(__graft_entry__.dryrun_lint) and fail on "
                         "unbaselined findings")
    args = ap.parse_args(argv)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res, stderr = run_dryrun(args.devices, repo)
    if args.lint:
        res["lint"] = run_lint(args.devices, repo)
    tpath = args.telemetry or (
        os.path.splitext(args.out)[0] + ".jsonl")
    try:
        emit_telemetry(tpath, res, stderr, repo)
        res["telemetry"] = tpath
    except Exception as e:      # the gate result must survive regardless
        print(f"multichip: telemetry emit failed: {e}", file=sys.stderr)
    with open(args.out, "w") as f:
        json.dump(res, f, indent=1)
    print(f"dryrun on {res['n_devices']} devices: rc={res['rc']} "
          f"remat_warnings={res['remat_warnings']} -> {args.out}")
    if res["rc"] != 0:
        sys.stderr.write(res["tail"] + "\n")
        return res["rc"]
    if res["remat_warnings"]:
        sys.stderr.write(
            f"FAIL: {res['remat_warnings']} involuntary full "
            "rematerialization warning(s) — a global-view op reached "
            "the SPMD partitioner (see parallel/dense_slab.py)\n")
        return 3
    if args.lint and not res["lint"]["ok"]:
        sys.stderr.write("FAIL: static-analysis leg found unbaselined "
                         "findings\n" + res["lint"]["tail"] + "\n")
        return 4
    return 0


if __name__ == "__main__":
    sys.exit(main())
