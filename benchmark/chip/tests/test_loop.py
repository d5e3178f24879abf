"""``run.Loop`` on a path that computes nothing: how many steps are in
flight, that it drains whatever ``ahead`` is, and that a failed step ends
it."""
import math
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
import run  # noqa: E402


class Counting:
    """``dispatch(i)`` hands out i, ``wait`` takes the oldest back; the loss
    of step i is 1 / (i + 1), or what ``losses`` says."""

    def __init__(self, losses=None):
        self.in_flight, self.most, self.losses = [], 0, losses or {}

    def dispatch(self, i):
        self.in_flight.append(i)
        self.most = max(self.most, len(self.in_flight))
        return i

    def wait(self, i):
        assert self.in_flight.pop(0) == i
        return self.losses.get(i, 1.0 / (i + 1))


@pytest.mark.parametrize("ahead", [1, 2, 3])
@pytest.mark.parametrize("count", [1, 5])
def test_the_loop_drains(ahead, count):
    path = Counting()
    loop = run.Loop(path, ahead)
    done, dispatch_s = loop.run(count=count)
    assert loop.failed == 0 and loop.attempted == count == loop.i
    assert len(done) == len(dispatch_s) == count and done == sorted(done)
    assert loop.losses == [1.0 / (i + 1) for i in range(count)]
    assert path.in_flight == [] and path.most == min(ahead, count)
    # and goes on where it stopped
    loop.run(count=2)
    assert loop.attempted == count + 2 and loop.failed == 0
    assert len(loop.losses) == count + 2


@pytest.mark.parametrize("ahead", [1, 2])
def test_a_step_that_is_not_finite_ends_the_loop(ahead):
    loop = run.Loop(Counting({2: math.nan}), ahead)
    loop.run(count=10)
    # the steps already in flight are waited for, nothing more is sent
    assert loop.failed == 1
    assert loop.attempted == len(loop.losses) == 3 + (ahead - 1)


@pytest.mark.parametrize("ahead", [1, 2])
def test_a_step_that_raises_counts_with_those_in_flight(ahead):
    class Raising(Counting):
        def wait(self, i):
            if i == 1:
                raise RuntimeError("lost")
            return super().wait(i)
    loop = run.Loop(Raising(), ahead)
    loop.run(count=10)
    # the step that raised, and what was in flight behind it
    assert loop.failed == ahead and len(loop.losses) == 1
