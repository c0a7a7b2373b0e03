"""utils/flags.py env_truthy: the ONE truthiness parser for the
DISTLEARN_TPU_* feature switches, and its two call sites."""

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


def test_fused_enabled_uses_shared_parser(monkeypatch):
    from distlearn_tpu.ops.fused_update import fused_enabled
    monkeypatch.setenv("DISTLEARN_TPU_FUSED", "OFF")
    assert fused_enabled() is False
    monkeypatch.setenv("DISTLEARN_TPU_FUSED", "1")
    assert fused_enabled() is True
    assert fused_enabled(override=False) is False   # explicit arg wins
