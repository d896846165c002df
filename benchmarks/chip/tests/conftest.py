"""CPU tests of the chip benchmark's pieces (run with an explicit path:
``python -m pytest benchmarks/chip/tests``)."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path.insert(0, CHIP)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(CHIP)),
                                "src"))
DATA = os.path.join(HERE, "data")
