"""The control: the reference in fp8, put in the program's place, must
read over the limit that the program's own runs stay under, on three
seeds (tiny size, CPU). The chip readings at the cells' own sizes are in
PERF.md."""
import pytest

import tiny


@pytest.mark.parametrize("seed", [11, 2**31 + 7, 4_000_000_001])
def test_control_fails_where_the_program_passes(seed):
    run = tiny.run(tiny.CLOSED, {"logit_gap_max": tiny.GAP_LIMIT,
                                 "tokens_checked_min": 100},
                   seed=seed, control=True)
    assert run.correct, run.compared
    assert run.replay["control_gaps"].max() > tiny.GAP_LIMIT
