"""`tests/ops/cells.py` for three of its seven cells, which the three files
divide by their compiles' seconds, not by kind (PR 61, alone on 8 cores:
gpt3-2.7b 17, nemotron 50, phi-4 41 | lfm2 52, moonlight 51 | qwen3-next 63,
smallthinker 29); and of `gpt3-2.7b`'s executable, compiled once, that it
holds the gradients once.
"""

import re

import jax
import pytest

from tests.ops import cells, test_remat_cells_b, test_remat_cells_c
from tests.ops.cells import v5e  # noqa: F401 (a fixture)

HERE = ("gpt3-2.7b", "nemotron-3-nano-30b-a3b", "phi-4-mini-flash")
assert sorted(HERE + test_remat_cells_b.HERE + test_remat_cells_c.HERE) \
    == sorted(cells.CELLS)       # every cell is one file's


@pytest.mark.parametrize("cell", HERE)
def test_cell_backward_holds_each_forward_kernel_once(v5e, compiled_for_tpu,
                                                      cell):
    cells.cell_backward_holds_each_forward_kernel_once(cell, v5e)


# Microbatch gradients accumulate inside each chunk's backward program: the
# running sum is a donated operand and comes back in its own buffers
# (execution/pipeline.py). At the `gpt3-2.7b.steady` cell's size (embedding,
# 3 blocks and head on one chip, microbatches of 4 x 1024, bfloat16 + remat)
# the gradients are 2 GB of float32 on a 16 GB chip: held once they leave
# room, held a second time (a donation that did not take, or a gradient set
# built in temporaries and added afterwards) they do not.
def test_cell_sized_backward_holds_the_gradients_once(v5e, compiled_for_tpu):
    st, params, compiled = cells.cell_backward("gpt3-2.7b", v5e)
    leaves = jax.tree.leaves(params)
    grad_bytes = sum(a.size * a.dtype.itemsize for a in leaves)

    header = compiled.as_text().split("\n", 1)[0]
    aliased = sorted(int(p) for p in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header))
    # Operands flatten as (params..., sum..., batch): every leaf of the sum.
    assert aliased == list(range(len(leaves), 2 * len(leaves))), header[:400]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= grad_bytes
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes)
    slack = 4 << 20      # the batch, the loss, tile padding of small leaves
    assert held <= 2 * grad_bytes + slack, mem   # parameters + ONE gradient set
    assert mem.temp_size_in_bytes < grad_bytes, mem

    fill = st.zero[0].lower(params).compile().memory_analysis()
    assert fill.argument_size_in_bytes == 0      # the parameters are not read
    assert fill.temp_size_in_bytes == 0
    assert grad_bytes <= fill.output_size_in_bytes <= grad_bytes + slack
