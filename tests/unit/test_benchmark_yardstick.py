"""The yardstick under the floor: tier-1 collects the benchmark's own tests.

``benchmark/tests/`` checks what every PR's numbers rest on: ``BENCHMARK.json``
against its contract, the reducers and ``reduce.py`` by hand-built traces, the
window rule, the traffic plans, and the second family's plain reference. They
live beside the benchmark, which no PR but a ``benchmark`` one may edit, so
this file only brings them into ``tests/``: their functions and fixtures, by
name. ``test_rehearsal.py`` (a CPU run of every cell, minutes) stays by hand.
"""

import os
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

pytest.register_assert_rewrite(
    "benchmark.tests.test_contract", "benchmark.tests.test_reduce",
    "benchmark.tests.test_reducers", "benchmark.tests.test_traffic",
    "benchmark.tests.test_window", "benchmark.tests.test_deepseek_v3",
    "benchmark.tests.test_ouro", "benchmark.tests.test_nemotron_h")

from benchmark.tests.test_contract import *  # noqa: E402,F401,F403
from benchmark.tests.test_deepseek_v3 import *  # noqa: E402,F401,F403
from benchmark.tests.test_nemotron_h import *  # noqa: E402,F401,F403
from benchmark.tests.test_ouro import *  # noqa: E402,F401,F403
from benchmark.tests.test_reduce import *  # noqa: E402,F401,F403
from benchmark.tests.test_reducers import *  # noqa: E402,F401,F403
from benchmark.tests.test_traffic import *  # noqa: E402,F401,F403
from benchmark.tests.test_window import *  # noqa: E402,F401,F403
