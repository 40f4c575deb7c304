"""Guards around ``chip_smoke.py`` that need no chip.

* the no-fallback guard: with ``JAX_PLATFORMS=cpu`` and default
  arguments the script must exit non-zero and print no ``"ok": true``
  line — a smoke that "passes" on the CPU hides the device;
* the MHD phases rehearse: the uniform MHD run through the command
  line at 32³ and the ``mhd-parity`` phase (the tiled CT kernel against
  ``mu.step``, a vmapped batch against solo runs) on a small box with
  the kernel interpreted — the control flow of what the chip run holds
  to the chip's own arithmetic;
* the native helpers are built from the committed source only: the
  binary's name carries a hash of ``ramses_native.cpp``, an absent
  hashed file is rebuilt, and a stale ``_ramses_native.so`` (the old
  mtime-keyed name) is never loaded.
"""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO,
                                                     "chip_smoke.py")],
                       env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0, r.stdout[-2000:]
    assert '"ok": true' not in r.stdout
    assert "no accelerator" in r.stderr


def test_mhd_phases_rehearse(tmp_path):
    code = (
        "import os, sys\n"
        "sys.path.insert(0, sys.argv[1])\n"
        "import chip_smoke as cs\n"
        "import ramses_tpu\n"
        "cs.OUT = sys.argv[2]\n"
        "os.chdir(cs.OUT)\n"
        "cs.phase_mhd(cs.shrunk(cs.NML_MHD, 5, 5, 4), True)\n")
    r = subprocess.run([sys.executable, "-c", code, REPO, str(tmp_path)],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert "[mhd] 32^3 f32 nstep=4" in r.stdout
    assert "[kernel] pallas_ct: not traced (XLA formulation)" in r.stdout
    assert "[mhd-parity] ok" in r.stdout
    assert r.stdout.count("vs its solo run, 4 steps: gap 0.000e+00") == 2


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")
def test_native_rebuilds_hashed_and_ignores_stale(tmp_path):
    """Work on a private copy of the native package so concurrent test
    workers never see the binary vanish."""
    pkg = tmp_path / "native_copy"
    shutil.copytree(os.path.join(REPO, "ramses_tpu", "native"), pkg,
                    ignore=shutil.ignore_patterns("*.so", "__pycache__"))
    stale = pkg / "_ramses_native.so"
    stale.write_bytes(b"not an ELF file")          # newer than the source
    code = (
        "import importlib.util, os, sys\n"
        "spec = importlib.util.spec_from_file_location(\n"
        "    'native_copy', os.path.join(sys.argv[1], '__init__.py'))\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(m)\n"
        "assert not os.path.exists(m.so_path())\n"
        "L = m.lib()\n"
        "assert L is not None, m.build_error\n"
        "assert os.path.exists(m.so_path())\n"
        "assert m.morton_encode(__import__('numpy').array(\n"
        "    [[1, 1, 1]], 'int64'), 3)[0] == 7\n"
        "print('SO', os.path.basename(m.so_path()))\n")
    r = subprocess.run([sys.executable, "-c", code, str(pkg)],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    name = r.stdout.split("SO ")[-1].strip()
    assert name.startswith("_ramses_native_") and name != stale.name
    # an edited source names (and builds) a different binary
    with open(pkg / "src" / "ramses_native.cpp", "a") as f:
        f.write("\n// edited\n")
    r2 = subprocess.run([sys.executable, "-c", code, str(pkg)],
                        capture_output=True, text=True, timeout=300)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert r2.stdout.split("SO ")[-1].strip() != name
