import os
import sys

# the benchmark's modules are imported as the `bench` package from the
# repository root
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
