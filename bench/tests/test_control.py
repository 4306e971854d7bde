"""The control comes out as not correct through the harness's own
comparison: a control run puts the reference, computed one precision
down, in the program's place (fp8 activations for the served model, one
significand bit less for the equalizer) and is judged by the same checks
and limits, while the program's own readings stay within them.  Small
sizes on the CPU; on the chip the same readings come from
`python3 -m bench.control` at the cells' own sizes."""
import pytest

from bench import run
from bench.tests import small


def _within(checks):
    return all(c["value"] <= c["limit"] for c in checks.values())


@pytest.mark.parametrize("seed", [5, 2 ** 33 + 1])
def test_served_model_control_is_not_correct(seed):
    result, _ = run.execute(small.serving_cell(), seed, 3.0, False,
                            control=True)
    assert result["notes"]["compared_tokens"] >= 100
    assert _within(result["program_checks"]), result["program_checks"]
    assert not result["correct"], result["checks"]
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("seed", [6, 2 ** 33 + 2])
def test_equalizer_control_is_not_correct(seed):
    result, _ = run.execute(small.equalizer_cell(), seed, 0.3, False,
                            control=True)
    assert _within(result["program_checks"]), result["program_checks"]
    assert not result["correct"], result["checks"]
    assert list(result)[-1] == "checks"
