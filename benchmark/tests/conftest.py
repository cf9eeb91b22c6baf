"""Puts the checkout on the path so that ``benchmark`` and ``racon_tpu``
import from it, wherever pytest is started."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
