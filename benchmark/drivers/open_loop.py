"""Driver `open_loop`: independent clients that each send a request when
they have one, whether or not earlier requests have been answered, and
wait for their own answer: how the clients of a resident service behave.

Requests are sent on a SCHEDULE. The schedule is a Poisson process:
exponential gaps drawn once from the mix's own ``arrival_seed`` at the
mix's ``rate_per_s``, so every run offers the same arrivals and ``--seed``
draws the right-hand sides and the judged sample only. The pool carries
the mix's arrival parameters as ``pool.arrivals`` (the builder puts them
there: `run` is handed nothing else of the mix).

Every request is one call of ``solve(request)`` on a thread of its own,
so each has its `bench:solve` span; ``t_issue`` is its SCHEDULED arrival
and ``t_done`` the moment the answer is in its client's hands, so a late
generator counts against the system. How late it ran is said on stderr,
in the mix's name. ``before(i)`` and ``after(i, k, x, info)`` run on the
ONE thread that also sends the requests (the caller's): ``before`` ahead
of each send, ``after`` in order of completion. Arrivals stop ``seconds``
after the first, and every request in flight is let finish.
"""
from __future__ import annotations

import queue
import sys
import threading
import time

import numpy as np


def schedule(rate_per_s: float, arrival_seed: int, seconds: float) -> list:
    """Arrival offsets in seconds from the first (which is 0.0), up to
    ``seconds``: a function of the rate, the seed and the cut alone. The
    gaps are drawn one by one from one generator, so a longer window
    extends a shorter one's schedule and changes none of it."""
    rng = np.random.default_rng(int(arrival_seed))
    out, t = [], 0.0
    while t <= seconds:
        out.append(t)
        t += float(rng.exponential(1.0 / float(rate_per_s)))
    return out


def run(solve, pool, seconds: float, before=None, after=None) -> list:
    """Returns one record per request, ordered by ``i``: ``{"i", "k",
    "t_issue", "t_done", "info"}`` (clock: `time.perf_counter`), the
    closed-loop driver's record."""
    arrivals = pool.arrivals
    offsets = schedule(
        arrivals["rate_per_s"], arrivals["arrival_seed"], seconds
    )
    answered: queue.SimpleQueue = queue.SimpleQueue()
    records, late = {}, []

    def client(i, k, t_issue):
        x, info = solve(pool[k])
        answered.put((i, k, t_issue, time.perf_counter(), x, info))

    def take(timeout):
        """One completion, if any comes within ``timeout`` seconds."""
        try:
            i, k, t_issue, t_done, x, info = answered.get(
                timeout=max(0.0, timeout)
            )
        except queue.Empty:
            return
        records[i] = {
            "i": i, "k": k, "t_issue": t_issue, "t_done": t_done,
            "info": info,
        }
        if after is not None:
            after(i, k, x, info)

    t_open = time.perf_counter()
    for i, offset in enumerate(offsets):
        t_issue = t_open + offset
        while time.perf_counter() < t_issue:
            take(t_issue - time.perf_counter())
        if before is not None:
            before(i)
        late.append(time.perf_counter() - t_issue)
        threading.Thread(
            target=client, args=(i, i % len(pool), t_issue), daemon=True,
            name=f"bench-client-{i}",
        ).start()
    while len(records) < len(offsets):
        take(1.0)
    print(
        f"bench: {arrivals.get('mix', 'open_loop')}: generator lateness "
        f"max {1e3 * max(late):.3f} ms mean "
        f"{1e3 * sum(late) / len(late):.3f} ms over {len(late)} arrivals "
        f"at {arrivals['rate_per_s']} a second",
        file=sys.stderr, flush=True,
    )
    return [records[i] for i in sorted(records)]
