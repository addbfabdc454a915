import os
import sys

# the program under test, as the benchmark's run.py puts it on the path
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))
