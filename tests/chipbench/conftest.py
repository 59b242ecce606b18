import os
import sys

# the benchmark package sits at the repository's root, the tiny-benchmark
# helper beside these tests
HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
for path in (REPO, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)
