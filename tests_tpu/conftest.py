"""TPU test lane: run with `python -m pytest tests_tpu/ -q` on a machine
with a TPU. Unlike tests/conftest.py this does NOT select the cpu
platform — the default backend (the TPU) stays available, and the tests
cross-check it against CPU-jax via check_consistency (the reference's
tests/python/gpu/test_operator_gpu.py pattern).

A machine without a TPU FAILS this lane: skipped tests would read as a
green run on the chip."""
import jax
import pytest


def pytest_collection_modifyitems(config, items):
    platform = jax.devices()[0].platform
    if platform != "tpu":
        pytest.exit(f"tests_tpu needs a TPU, but jax's default backend is "
                    f"{platform!r}: refusing to skip the chip lane",
                    returncode=1)
