import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

# the Pallas kernels run on the CPU in interpret mode, which they only do
# when asked (subprocesses inherit the opt-in)
os.environ.setdefault("REPRO_PALLAS_INTERPRET", "1")
