"""chipbench's own checks: ``python -m pytest chipbench/tests -q`` (tier-1
collects ``tests/`` only, so these do not move its count)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
