"""The benchmark's tests import ``bench`` from the root of the checkout."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
