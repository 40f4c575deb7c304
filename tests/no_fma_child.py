"""A child Python process whose CPU backend has no FMA to contract with.

XLA's CPU backend contracts ``a * b + c`` into one fused multiply-add
wherever both land in one fusion (and which do depends on how a loop is
vectorised, so on the shape), so two spellings of one expression graph, or
one spelling at two shapes, differ in the last bit there.  Comparisons that
must hold TO THE BIT run a test file as a script in a child capped below
FMA (``--xla_cpu_max_isa=SSE4_2``); the script prints one line ``RESULT
<json>``.
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def start(script: str, *args: str) -> subprocess.Popen:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS="--xla_cpu_max_isa=SSE4_2")
    return subprocess.Popen([sys.executable, os.path.abspath(script), *args],
                            env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)


def result(proc: subprocess.Popen) -> dict:
    """The child's ``RESULT`` object (waits for it to end)."""
    out, err = proc.communicate(timeout=900)
    line = [ln for ln in out.splitlines() if ln.startswith("RESULT ")]
    assert proc.returncode == 0 and line, err[-3000:]
    return json.loads(line[-1][len("RESULT "):])
