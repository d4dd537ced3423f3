"""The benchmark's own tests run on the CPU at a rehearsal's size:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

They are not tier-1 tests (tier-1 is ``tests/``): each drives a whole
run of ``run.py`` and takes half a minute."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
