"""Entry point: one run of one cell (see perfbench/harness.py)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from perfbench import harness
    sys.exit(harness.main(sys.argv[1:]))
