import numpy as np
import pytest


@pytest.fixture(scope="session", autouse=True)
def _tracer_leak_lane():
    """Opt-in leak-hunting lane: REPRO_CHECK_TRACER_LEAKS=1 runs the whole
    suite under jax_check_tracer_leaks (rule F1's runtime twin — catches
    traced values escaping their trace). Off by default: leak checking
    disables some tracing fast paths and slows the suite noticeably."""
    from repro.analysis.guards import tracer_leak_lane_enabled

    if not tracer_leak_lane_enabled():
        yield
        return
    import jax

    jax.config.update("jax_check_tracer_leaks", True)
    try:
        yield
    finally:
        jax.config.update("jax_check_tracer_leaks", False)


@pytest.fixture
def rng():
    return np.random.default_rng(0)
