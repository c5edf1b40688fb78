"""Driver `closed_loop`: one client that waits for each answer before it
sends the next request, which is how a caller of a solver library behaves.

The loop cycles through the pool, issues solves until ``seconds`` have
passed since the first issue, and lets the one in flight finish. Every
solve is one call of ``solve(request)``, which returns ``(x, info)`` with
the answer on the host, so the host clock around it covers the device work.
"""
from __future__ import annotations

import time


def run(solve, pool, seconds: float, before=None, after=None) -> list:
    """Returns one record per solve: ``{"i", "k", "t_issue", "t_done",
    "info"}`` (clock: `time.perf_counter`). ``before(i)`` runs ahead of a
    solve's issue and ``after(i, k, x, info)`` behind its completion, both
    outside that solve's own time but inside the window: the harness hangs
    its trace and its sample of answers there."""
    records = []
    t_open = time.perf_counter()
    i = 0
    while True:
        k = i % len(pool)
        if before is not None:
            before(i)
        t_issue = time.perf_counter()
        x, info = solve(pool[k])
        t_done = time.perf_counter()
        records.append(
            {"i": i, "k": k, "t_issue": t_issue, "t_done": t_done, "info": info}
        )
        if after is not None:
            after(i, k, x, info)
        del x
        i += 1
        if time.perf_counter() - t_open >= seconds:
            return records
