"""Run by hand: ``python -m pytest benchmark/tests`` from the checkout's root.
Outside tier-1's ``tests/`` on purpose: these check the yardstick, not the
package, and add nothing to the suite's clock."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
