"""The control at a size a test run can hold: the reference at fp8 put in
the program's place reads far over the limit that sound runs of the
program stay under.  At the cells' own size the same readings come from
``benchmarks/chip/control.py`` on the chip."""
import contextlib
import io

import jax
import pytest

import control
import program


@pytest.mark.parametrize("seed", [3, 2**32 + 8])
def test_control_fails_where_the_program_passes(cell_factory, seed):
    cell = cell_factory()
    # some hundreds of checked tokens, as in a cell's check
    cell.mix["output"] = {"median": 32, "sigma": 0.3, "min": 16, "max": 48}
    cell.params["n_check"] = 6
    limit = cell.params["check"]["served_logit_gap"]
    with contextlib.redirect_stderr(io.StringIO()):
        r = control.readings(cell, seed, 2.0, jax.devices()[0],
                              program.CompileClock())
    prog, ctrl = r["program"], r["control"]
    assert prog["unfinished_requests"] == 0
    assert prog["checked_tokens"] > 150
    assert prog["correct"] and prog["served_logit_gap"] <= limit
    assert not ctrl["correct"] and ctrl["served_logit_gap"] > 3 * limit
