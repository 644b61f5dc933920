import sys

from bench.harness import ROOT

# the program under test, as the benchmark's own command finds it
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))
