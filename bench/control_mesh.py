#!/usr/bin/env python3
"""The mesh cell's control of `correct`: bench/control.py with one more
break, since a mesh stream's early batches never reach the balance cap and
doubling the slack there changes no label.

  rounds  one refinement round fewer (refine_rounds - 1)

    python3 bench/control_mesh.py --workload mesh-tri1024.natural \\
        --control rounds --seconds 12 --seeds 5 6 7

takes bench/control.py's arguments and prints its lines; `eps` and `none`
are there too.
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import control  # noqa: E402

control.BROKEN["rounds"] = lambda s: dataclasses.replace(
    s, refine_rounds=s.refine_rounds - 1)

if __name__ == "__main__":
    sys.exit(control.main())
