"""``python3 -m bench.control`` for cells of the ``train_large`` driver:
the reference's steps are that driver's ``run_steps``, which fit one chip
beside nothing else.

    python3 bench/tests/control_large.py --workload <name> --seeds 1 2 3
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import control  # noqa: E402
from bench.drivers.train_large import lean_reference  # noqa: E402

if __name__ == "__main__":
    with lean_reference():
        sys.exit(control.main())
