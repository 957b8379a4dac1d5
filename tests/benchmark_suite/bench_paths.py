"""Shared by the benchmark's tests: the checkout on sys.path."""

import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SUITE = os.path.dirname(os.path.abspath(__file__))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)
