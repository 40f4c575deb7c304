#!/usr/bin/env python3
"""Sandbox rehearsal of the whole command: tiny levels (each configuration's
``rehearse`` block), Pallas kernels interpreted or off, whatever backend
``JAX_PLATFORMS`` gives.  It exercises every path of ``run.py`` and can never
print a result line: its last line starts with ``REHEARSAL``.

    JAX_PLATFORMS=cpu python benchmark/rehearse.py --workload <cell> --seed 1 --seconds 2 --trace 0
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run  # noqa: E402

if __name__ == "__main__":
    run.main(rehearse=True)
