"""The benchmark's own tests: run by hand with ``pytest perfbench/tests -q`` on
the CPU. They are not part of the repository's tier-1 suite."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
