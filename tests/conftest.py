import jax
import pytest

try:
    # CPU-only container: keep hypothesis fast and quiet when present.
    from hypothesis import settings

    settings.register_profile("ci", max_examples=15, deadline=None)
    settings.load_profile("ci")
except ImportError:
    # Tier-1 runs without hypothesis; property tests skip via tests/_hyp.py.
    pass

jax.config.update("jax_enable_x64", False)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: a long test (subprocess with forced host devices)")


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)
