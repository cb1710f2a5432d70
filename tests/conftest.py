import os
import sys

# tests must see exactly ONE device
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np
import pytest

from repro.models.transformer import ModelConfig, TransformerAdapter, init_params


def hypothesis_or_stubs():
    """``(given, settings, st)`` from hypothesis, or skip-stubs without it.

    Property tests are marked skipped when hypothesis isn't installed instead
    of erroring the whole module at collection (the CI image installs it via
    requirements-dev.txt; minimal environments may not).
    """
    try:
        from hypothesis import given, settings
        from hypothesis import strategies as st
        return given, settings, st
    except ImportError:
        def settings(*a, **k):
            return lambda f: f

        def given(*a, **k):
            return lambda f: pytest.mark.skip(
                reason="hypothesis not installed (pip install -r requirements-dev.txt)")(f)

        class _Strategies:
            def __getattr__(self, name):
                return lambda *a, **k: None

        return given, settings, _Strategies()


@pytest.fixture(scope="session")
def tiny_cfg():
    return ModelConfig(name="tiny", arch_type="dense", n_layers=2, d_model=64,
                       n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                       vocab_size=97)


@pytest.fixture(scope="session")
def tiny_params(tiny_cfg):
    return init_params(jax.random.PRNGKey(0), tiny_cfg)


@pytest.fixture(scope="session")
def tiny_adapter(tiny_cfg):
    return TransformerAdapter(tiny_cfg)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
