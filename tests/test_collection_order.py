"""The harness itself (`tests/conftest.py`): what it hands out first is
there to hand out, pytest-xdist keeps its order, and this process builds
and runs its XLA:CPU programs as it says.
"""

import os
from pathlib import Path

import jax

from tests import conftest


def test_the_files_handed_out_first_hold_tests_and_both_flags_are_set(request):
    # A file renamed or emptied falls back into the alphabet without a word.
    root = Path(__file__).resolve().parents[1]
    for path in conftest.DEAREST_FIRST:
        assert "\ndef test_" in (root / path).read_text(), path
    assert len(set(conftest.DEAREST_FIRST)) == len(conftest.DEAREST_FIRST)
    assert request.config.option.loadscopereorder is False
    assert set(conftest.XLA_FLAGS) <= set(os.environ["XLA_FLAGS"].split())
    assert jax.config.read("jax_cpu_enable_async_dispatch") is False
