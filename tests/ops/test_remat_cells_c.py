"""`tests/ops/cells.py` for `qwen3-next-80b-a3b` and `smallthinker-21b-a3b`."""

import pytest

from tests.ops import cells
from tests.ops.cells import v5e  # noqa: F401 (a fixture)

HERE = ("qwen3-next-80b-a3b", "smallthinker-21b-a3b")


@pytest.mark.parametrize("cell", HERE)
def test_cell_backward_holds_each_forward_kernel_once(v5e, compiled_for_tpu,
                                                      cell):
    cells.cell_backward_holds_each_forward_kernel_once(cell, v5e)
