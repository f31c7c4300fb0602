"""`tests/ops/cells.py` for `lfm2-24b-a2b` and `moonlight-16b-a3b`."""

import pytest

from tests.ops import cells
from tests.ops.cells import v5e  # noqa: F401 (a fixture)

HERE = ("lfm2-24b-a2b", "moonlight-16b-a3b")


@pytest.mark.parametrize("cell", HERE)
def test_cell_backward_holds_each_forward_kernel_once(v5e, compiled_for_tpu,
                                                      cell):
    cells.cell_backward_holds_each_forward_kernel_once(cell, v5e)
