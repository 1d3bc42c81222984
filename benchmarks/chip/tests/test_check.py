"""The check's sample and the host-clock readers on hand-made requests of
unequal lengths, as an engine that is not lock-step would serve them."""
import numpy as np

import check
from harness import Request, load_module
from conftest import BENCH_DIR


def req(slot, submit, n, want, step=0.1):
    times = submit + 0.5 + step * np.arange(n)
    return Request(slot=slot, submit=submit, prompt=np.zeros(4, np.int32),
                   tokens=np.ones(n, np.int32), times=times, want=want)


REQUESTS = [req(0, 0.0, 8, 8), req(1, 0.0, 3, 3), req(2, 0.1, 5, 5), req(3, 0.1, 2, 9),
            req(0, 1.5, 4, 4), req(2, 2.0, 6, 6), req(3, 2.0, 1, 5)]


def test_sample_takes_the_longest_and_its_neighbours_in_time_over_the_slots():
    # slot 0's 8 tokens are the longest; slots 1 and 2 give the requests
    # nearest it in time; slot 3 finished nothing and is left out
    for seed in range(20):
        chosen = check.sample(REQUESTS, 4, seed)
        assert [(r.slot, r.submit, len(r.tokens)) for r in chosen] == \
            [(0, 0.0, 8), (1, 0.0, 3), (2, 0.1, 5)]


def test_sample_of_nothing_finished_is_empty():
    assert check.sample([req(0, 0.0, 2, 9)], 4, 1) == []


class _Run:
    requests, start, end = REQUESTS, 0.0, 0.95
    seconds = 0.95


def test_readers_count_only_what_reached_the_host_inside_the_window():
    tok_s = load_module(BENCH_DIR / "metrics/output_tok_s.py").read(_Run)
    # tokens at 0.5 + 0.1 k (submit 0) and 0.6 + 0.1 k (submit 0.1), up to 0.95
    assert tok_s == (5 + 3 + 4 + 2) / 0.95
    ttft = load_module(BENCH_DIR / "metrics/ttft_p95_ms.py").read(_Run)
    assert abs(ttft - 500.0) < 1e-6
    itl = load_module(BENCH_DIR / "metrics/itl_p95_ms.py").read(_Run)
    assert abs(itl - 100.0) < 1e-6
