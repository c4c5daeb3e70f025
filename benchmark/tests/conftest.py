"""The benchmark's own tests run on the CPU at small sizes:
``python -m pytest benchmark/tests`` from the root of the repo."""

import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: the cell's configuration cut to a CPU test's size
SMALL = {"events": 2000, "r_cap": 256, "sched_rows": 1280}


@pytest.fixture
def small_cell(monkeypatch):
    """Skip the harness's look for a chip and cut the configuration to
    SMALL; the rest of a run is the command line's."""
    from benchmark import common, run

    load = common.load_json

    def load_small(path):
        data = load(path)
        if os.sep + "configs" + os.sep in path:
            data.update(SMALL)
        return data

    monkeypatch.setattr(run, "_tpu_devices", lambda chips: None)
    monkeypatch.setattr(common, "load_json", load_small)
