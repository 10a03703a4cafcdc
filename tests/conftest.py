import os
import sys

import pytest

# tests run against a single CPU device; the 512-device dry-run is
# exercised via subprocess (test_dryrun_mechanism) so it never leaks
# XLA_FLAGS into this process.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture(scope="session")
def tiny_training_data():
    from repro.core.ml.dataset import collect_training_data
    return collect_training_data(reps=6, duration_s=45.0, seed=0)


@pytest.fixture(scope="session")
def tiny_models():
    """The production GBDT pair (paper §IV-B protocol), disk-cached — the
    same models the benchmarks deploy, so system tests exercise the real
    confidence levels of the tau=0.8 gate."""
    from repro.core.ml.train import get_default_models
    m_r, m_w = get_default_models()
    return {"read": m_r, "write": m_w}
