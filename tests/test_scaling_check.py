"""scripts/scaling_check.py tier-1 wiring (ISSUE 11): chunked parity
end-to-end through train_pass on the in-process CPU mesh."""

import importlib.util
import os

import pytest

REPO = os.path.join(os.path.dirname(__file__), os.pardir)


def _load(name, rel):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def sc():
    return _load("scaling_check", os.path.join("scripts",
                                               "scaling_check.py"))


def test_chunked_parity_through_train_pass(sc):
    """a2a_chunks=2 == a2a_chunks=1 digest, bit for bit, ×2 seeded
    runs — on this process's 8-device mesh (conftest)."""
    ok = sc.parity_check(rows_per_file=400)
    if ok is None:
        pytest.skip("no multi-device mesh in this process")
    assert ok is True
