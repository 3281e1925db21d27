import os
import sys

# The program under test lives in src/; the benchmark imports it as run.py does.
_SRC = os.path.normpath(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                     "..", "..", "src"))
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
