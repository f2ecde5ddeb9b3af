import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
REPO = os.path.dirname(os.path.dirname(CHIP))
for p in (CHIP, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
