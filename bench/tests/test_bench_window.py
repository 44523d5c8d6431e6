"""The serve driver's window reduction from its own token records: TTFT
from the scheduled arrival (a request still waiting counts its wait so
far), gaps between tokens, and the context length of each token a decode
produced."""
import pytest

from bench import generator
from bench.drivers import serve_open_loop as drv


class _Req:
    def __init__(self, out, done):
        self.out, self.done = out, done


def _loop():
    loop = drv.OpenLoop.__new__(drv.OpenLoop)
    a = drv.Track(generator.Due(0, 1.0, [1] * 10, 5), _Req([7] * 4, True),
                  times=[1.5, 2.0, 2.5, 4.0])
    b = drv.Track(generator.Due(1, 3.0, [1] * 6, 5), _Req([], False))
    loop.tracks = {0: a, 1: b}
    loop.occupancy = [(0.5, 0, 0), (2.2, 1, 12), (2.7, 1, 13), (9.0, 3, 50)]
    return loop


def test_window_reduction_from_the_runs_own_records():
    m = drv.window_metrics(_loop(), 1.0, 3.5)
    # a: due 1.0, first token 1.5; b: due 3.0, no token by the close 3.5
    assert m["ttft"] == pytest.approx([0.5, 0.5])
    assert m["gaps"] == pytest.approx([0.5, 0.5])   # 2.0 and 2.5 in window
    assert m["tokens"] == 3
    # tokens 1 and 2 of a decode over its 10-token prompt and 1, 2 tokens
    assert m["decode_lengths"] == [11, 12]
    assert m["decoding_mean"] == 1.0 and m["pool_tokens_mean"] == 12.5
