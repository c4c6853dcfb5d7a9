"""The benchmark's own tests, run on a CPU with an explicit path:

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))
