"""Test harness config: tests run on JAX's CPU backend. They never need a
chip: `benchmark/run.py` is what runs the program on one.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"

import pathlib
import sys

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

REFERENCE = pathlib.Path("/root/reference")

import pytest


@pytest.fixture(scope="session")
def reference_fixtures():
    """Paths to the reference repo's checked-in golden binary fixtures."""
    if not REFERENCE.exists():
        pytest.skip("reference repo not mounted")
    return {
        "ec_dat": REFERENCE / "weed/storage/erasure_coding/1.dat",
        "ec_idx": REFERENCE / "weed/storage/erasure_coding/1.idx",
        "needle_dat": REFERENCE / "weed/storage/needle/43.dat",
        "idx_187": REFERENCE / "test/data/187.idx",
    }
