"""The control fails the check, and the program passes it, at a size a
CPU test holds: the reference put in the program's place one precision
step down (fp8 matrix products in the embedder) reads above the limit
that the program reads under."""
import pytest

import control
from tiny import tiny_cell


@pytest.mark.parametrize("name,number", [("chat.repeat", "embed_gap"),
                                         ("chat.single", "embed_gap")])
def test_control_fails_where_the_program_passes(name, number):
    cell = tiny_cell(name)
    r = control.readings(cell, 2 ** 37 + 5, 1.0)
    limit = cell.config["limits"][number]
    assert r[number] <= limit
    assert r["control_" + number] > limit
