"""Each job's control (the reference in bfloat16, below the
configuration's float32) fails the job's comparison on every seed; the
float64 reference against itself passes."""
import pytest

from perfbench.tests.conftest import JOBS, job

# the controls need sums that bfloat16 cannot hold: counts past 256 and
# ranks summed over many in-edges
SIZES = {"wordcount": {"documents": 8192, "min_words": 10,
                       "max_words": 100, "vocab": 1000},
         "pagerank": {"scale": 12, "row_width": 64}}


@pytest.mark.parametrize("seed", [5, 77, 2**31 + 3])
@pytest.mark.parametrize("kind", sorted(JOBS))
def test_control_is_not_correct(kind, seed):
    j = job(kind, seed, **SIZES[kind])
    j.records(300)
    checks = j.compare(j.control(300), j.reference(300))
    assert not all(v <= lim for v, lim in checks.values()), checks


@pytest.mark.parametrize("kind", sorted(JOBS))
def test_reference_against_itself_is_correct(kind):
    j = job(kind, 11)
    j.records(30)
    checks = j.compare(j.reference(30), j.reference(30))
    assert all(v <= lim for v, lim in checks.values())
