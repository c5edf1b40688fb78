"""Driver `open_loop` by itself, with a stand-in for the solver: the
schedule, the clocks, the threads and the order of what it hands back."""
import threading
import time

import numpy as np
import pytest

from benchmark.drivers import open_loop


class Pool(list):
    arrivals = {}


def pool_of(n, rate, arrival_seed=11):
    pool = Pool(range(n))
    pool.arrivals = {
        "mix": "a_mix", "rate_per_s": rate, "arrival_seed": arrival_seed,
    }
    return pool


def test_the_schedule_is_a_function_of_the_seed_and_the_rate_alone():
    a = open_loop.schedule(50.0, 11, 2.0)
    assert a == open_loop.schedule(50.0, 11, 2.0)
    assert a != open_loop.schedule(50.0, 12, 2.0)
    assert a[0] == 0.0 and a == sorted(a) and a[-1] <= 2.0
    # a longer window extends the schedule and changes none of it
    longer = open_loop.schedule(50.0, 11, 4.0)
    assert longer[: len(a)] == a and len(longer) > len(a)
    # the same draws at another rate: the gaps scale
    slow = open_loop.schedule(25.0, 11, 4.0)
    assert np.allclose(np.diff(slow)[:20], 2.0 * np.diff(a)[:20])
    # exponential gaps of mean 1 / rate
    gaps = np.diff(open_loop.schedule(50.0, 11, 400.0))
    assert abs(gaps.mean() - 0.02) < 0.001 and abs(gaps.std() - 0.02) < 0.002


def test_requests_go_out_on_schedule_whether_or_not_earlier_ones_are_answered(capsys):
    """Every answer takes 0.2 s and arrivals come 100 a second: a closed
    loop would send 3 in the window, the open loop sends them all, each on
    a thread of its own, and stamps each with its SCHEDULED arrival."""
    main = threading.get_ident()
    seen = {"solve": set(), "before": [], "after": []}

    def solve(req):
        seen["solve"].add(threading.current_thread().name)
        time.sleep(0.2 if req else 0.05)  # request 0 overtakes its elders
        return f"x{req}", {"converged": True, "iterations": 1}

    def before(i):
        seen["before"].append((i, threading.get_ident()))

    def after(i, k, x, info):
        seen["after"].append((i, k, x, threading.get_ident(), time.perf_counter()))

    pool = pool_of(5, 100.0)
    t0 = time.perf_counter()
    records = open_loop.run(solve, pool, 0.5, before=before, after=after)
    t_return = time.perf_counter()
    offsets = open_loop.schedule(100.0, 11, 0.5)
    n = len(offsets)
    assert n > 30 and len(records) == n
    # records by i, k cycling through the pool, t_issue the scheduled arrival
    assert [r["i"] for r in records] == list(range(n))
    assert [r["k"] for r in records] == [i % 5 for i in range(n)]
    issued = np.array([r["t_issue"] for r in records])
    assert np.allclose(issued - issued[0], offsets, atol=1e-9)
    assert 0.0 <= issued[0] - t0 < 0.05
    # one thread a request, none of them the caller's; before and after on
    # the caller's thread alone
    assert seen["solve"] == {f"bench-client-{i}" for i in range(n)}
    assert [i for i, _ in seen["before"]] == list(range(n))
    assert {t for _, t in seen["before"]} == {main}
    assert {t for *_, t, _ in seen["after"]} == {main}
    # after runs in order of completion, with that request's own answer
    done_order = sorted(records, key=lambda r: r["t_done"])
    assert [i for i, *_ in seen["after"]] == [r["i"] for r in done_order]
    assert [i for i, *_ in seen["after"]] != list(range(n))  # 5, 10, ... overtook
    assert all(x == f"x{k}" for _i, k, x, *_ in seen["after"])
    # arrivals stop `seconds` after the first; every request in flight is
    # let finish, and the window closes after the last completion
    assert issued[-1] - issued[0] <= 0.5
    last_done = max(r["t_done"] for r in records)
    assert issued[-1] + 0.04 < last_done <= t_return
    assert all(r["t_done"] - r["t_issue"] >= 0.05 for r in records)
    # how late the generator ran is said, in the mix's name
    err = capsys.readouterr().err
    assert "bench: a_mix: generator lateness max " in err
    assert f"over {n} arrivals at 100.0 a second" in err


def test_a_late_generator_counts_against_the_system():
    """`before` holds the sending thread for 50 ms once: the requests
    behind it go out late, keep their scheduled `t_issue`, and so read
    longer than the 10 ms their answers took."""
    def before(i):
        if i == 3:
            time.sleep(0.05)

    def solve(req):
        time.sleep(0.01)
        return None, {"converged": True}

    records = open_loop.run(solve, pool_of(2, 200.0), 0.1, before=before)
    times = [r["t_done"] - r["t_issue"] for r in records]
    assert max(times[:3]) < 0.04 < times[3]
    offsets = open_loop.schedule(200.0, 11, 0.1)
    issued = [r["t_issue"] - records[0]["t_issue"] for r in records]
    assert issued == pytest.approx(offsets, abs=1e-9)
