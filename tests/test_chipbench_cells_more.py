"""``test_chipbench_cells.py``'s cases on the linear-attention and the
block-diffusion cell: a file of its own, so that ``loadfile`` runs the two
halves beside each other."""

import pytest

import test_chipbench_cells as cells  # beside this file


@pytest.fixture(scope="module", params=cells.MORE)
def cell(request):
    return request.param


# its ``line`` fixture (one traced rehearsal a cell) and its tests, under
# their own names
globals().update({name: thing for name, thing in vars(cells).items()
                  if name == "line" or name.startswith("test_")})
