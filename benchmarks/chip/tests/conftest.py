import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src")]
