import pytest

pytest.register_assert_rewrite("tests.ops.cells")   # its readers assert
