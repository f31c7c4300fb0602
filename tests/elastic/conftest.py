"""Minimal asyncio test support (pytest-asyncio is not in this image)."""

import asyncio
import inspect

import pytest

from oobleck_tpu.utils import metrics


def pytest_pyfunc_call(pyfuncitem):
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(asyncio.wait_for(fn(**kwargs), timeout=30))
        return True
    return None


@pytest.fixture(autouse=True)
def _no_measured_history():
    """The master's policy scores its recovery arms from latency histograms
    in the PROCESS-GLOBAL metrics registry (policy/signals.py); whatever ran
    earlier in this process — an engine test that timed a compile-laden
    reroute — must not decide which verb a control-plane test sees."""
    metrics.registry().clear()
    yield
