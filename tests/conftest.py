try:
    import jax
except ImportError:
    # CI fast job installs numpy+pytest only; the core schedule/IR tests
    # never touch jax, and the tests that do import it fail at import time
    # with a clear error if collected without it.
    jax = None

if jax is not None:
    # 8 virtual CPU devices for the shard_map / pjit distribution tests.
    # (The 512-device override is dryrun.py-only, per the launch design.)
    jax.config.update("jax_num_cpu_devices", 8)

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: paper-scale (p=1152) cells excluded from tier-1"
    )


def pytest_collection_modifyitems(config, items):
    if config.getoption("-m"):
        return
    skip_slow = pytest.mark.skip(reason="slow: run with -m slow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)
