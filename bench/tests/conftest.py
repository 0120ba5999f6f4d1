import sys
from pathlib import Path

# the program is imported from src/, as bench/run.py does
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))
