"""Every limit of every cell lies between the two chip readings it was set
from (benchmark/limits/<cell>.json, PERF.md section 6), with room on both
sides, and the control's readings fail the committed limits. The checks
themselves are conftest.py's, which test_adding_a_configuration.py also
makes of a manifest that has grown by two cells."""

import pytest

from benchmark.manifest import Manifest

from conftest import LIMITS_CHECKS, cell_names

MAN = Manifest()
CELLS = cell_names(MAN)


def _test_of(check):
    @pytest.mark.parametrize("cell", CELLS)
    def test(cell):
        check(MAN, cell)
    return test


# One test to a check of conftest.py's list, named after it: test_<what>
# for check_<what>, a case to a cell.
for _check in LIMITS_CHECKS:
    globals()["test_" + _check.__name__.removeprefix("check_")] = _test_of(
        _check)
