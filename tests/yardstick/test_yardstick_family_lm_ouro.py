"""benchmarks/tests/test_family_lm_ouro.py in tier-1 (see this package)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.tests.test_family_lm_ouro import *  # noqa: E402,F401,F403
