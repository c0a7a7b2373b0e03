"""utils/platform.py: the flag-replacement helper every entry point leans
on (a stale pre-set count silently overriding the request was a real bug
class — examples, dryrun)."""

import os

from distlearn_tpu.utils.platform import set_host_device_count


def test_set_host_device_count_replaces_stale_flag(monkeypatch):
    monkeypatch.setenv(
        "XLA_FLAGS",
        "--xla_foo=1 --xla_force_host_platform_device_count=2 --xla_bar=2")
    set_host_device_count(8)
    flags = os.environ["XLA_FLAGS"].split()
    assert "--xla_force_host_platform_device_count=8" in flags
    assert "--xla_force_host_platform_device_count=2" not in flags
    assert "--xla_foo=1" in flags and "--xla_bar=2" in flags   # preserved


def test_set_host_device_count_from_empty(monkeypatch):
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    set_host_device_count(4)
    assert os.environ["XLA_FLAGS"] == \
        "--xla_force_host_platform_device_count=4"
