import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
for p in (HERE.parents[1] / "src", HERE.parent, HERE):
    sys.path.insert(0, str(p))
