import sys

import numpy as np
import pytest

# NOTE: no XLA_FLAGS here — smoke tests and benches must see 1 device;
# only launch/dryrun.py forces 512 host devices (in its own process).

try:  # real hypothesis (declared in pyproject [test]) when available
    import hypothesis  # noqa: F401
except ImportError:  # hermetic containers: register the minimal fallback
    import _hypothesis_fallback

    sys.modules["hypothesis"] = _hypothesis_fallback


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def one_hot_state_access(monkeypatch):
    """Run the sweep's XLA lane program in the one-hot state-access form,
    which only a TPU lowering picks, on whatever platform the tests run."""
    import functools

    from repro.core import sweep
    from repro.core.lane_program import ONE_HOT_ACCESS, step_access

    monkeypatch.setattr(sweep, "_lane_step", functools.partial(
        step_access, access=ONE_HOT_ACCESS))
    # the jitted program caches its trace: drop it on the way in and out
    sweep._run_lanes_jit.clear_cache()
    yield
    sweep._run_lanes_jit.clear_cache()
