"""The benchmark's own tests run on the CPU backend at a size a test can hold
(``python3 -m pytest perfbench/tests -q``). They are not part of the repo's
tier-1 suite and never touch a chip."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from perfbench import harness  # noqa: E402

CELLS = ("criteo_pa_2e28.train_sat", "criteo_pa_2e28.serve_paced")
SCALE = harness.load_cell(CELLS[0])["kind"].TINY
