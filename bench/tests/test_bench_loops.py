"""The closed loop's stream covers its window: a window of ``seconds``
gets ``max_ticks_per_s`` ticks a second of stream, rounded up."""
import pytest

from bench import harness
from bench.tests import tiny


@pytest.mark.parametrize("seconds, ticks", [(30, 1800), (50, 3000),
                                            (0.1, 6)])
def test_closed_stream_covers_the_window(seconds, ticks):
    closed = harness.load_module(tiny.ROOT, "loops", "closed")
    assert closed.length({"max_ticks_per_s": 60}, seconds) == ticks
