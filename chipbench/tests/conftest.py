"""Run by path, on the CPU: ``JAX_PLATFORMS=cpu python -m pytest
chipbench/tests``.  The repository's ``testpaths`` keep these out of its
own test run."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
