#!/usr/bin/env python
"""padur — crash-durability drills for the front door.

The proof harness of `partitionedarrays_jl_tpu.frontdoor.journal`: a
gate that journals every request lifecycle transition ahead of the
client ack must survive its own death — kill -9 the serving process
mid-slab, restart against the same journal + checkpoint directories,
and every admitted request either completes BITWISE equal to its solo
solve or fails typed: zero lost, zero duplicated (a retried
idempotency-key submit returns the original id and result).

Usage:
    python tools/padur.py serve --journal-dir D [--checkpoint-dir C]
        [--port 0] [--url-file F] [--slab-delay 0.0] [--shed-depth N]
    python tools/padur.py --check          # tier-1 smoke (in-process)
    python tools/padur.py --drill          # full SIGKILL drill
                                           # (subprocess; -m slow)

``serve`` runs one demo Poisson tenant behind the HTTP gate with the
journal enabled, recovers any prior journal on startup, writes its URL
to ``--url-file``, and shuts down gracefully on SIGTERM/SIGINT
(drain-or-checkpoint — the `serve_until_signalled` exit-code contract:
0 after a clean signalled shutdown). ``--slab-delay`` stretches each
block solve so a drill can land SIGKILL mid-slab deterministically.

``--check`` is the fast in-process smoke wired into tier-1: journal
append/rotate/replay round-trip, one forced torn-tail recovery, one
mid-file corruption refusal, and a gate journal round trip with an
idempotency-key replay across a simulated crash.

``--drill`` is the real thing (registered under the ``slow`` pytest
marker): SIGKILL the serving subprocess mid-slab over HTTP, restart it
on the same journal, and assert the zero-lost / zero-duplicated /
bitwise-or-typed contract end to end.
"""
import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: The drill tenant: one Poisson operator (sequential backend — the
#: journal is host-side policy; the backend is whatever tenants run).
DRILL_GRID = (12, 12)
DRILL_TENANT = "poisson12"


def build_drill_gate(journal_dir, checkpoint_dir=None, shed_depth=4096,
                     slab_delay=0.0, start_workers=True):
    """One-tenant demo gate with the journal enabled; recovers any
    prior journal (tenants must be registered first — operators are
    code, not journal payload). ``slab_delay`` sleeps inside every
    block solve so a SIGKILL can land mid-slab."""
    import partitionedarrays_jl_tpu as pa
    from partitionedarrays_jl_tpu.frontdoor import Gate
    from partitionedarrays_jl_tpu.models import assemble_poisson

    if checkpoint_dir is None:
        checkpoint_dir = os.path.join(journal_dir, "svc-ckpt")
    A, b, xe, x0 = pa.prun(
        lambda parts: assemble_poisson(parts, DRILL_GRID),
        pa.sequential, (2, 2),
    )
    gate = Gate(
        journal_dir=journal_dir, checkpoint_dir=checkpoint_dir,
        shed_watermark=shed_depth, start_workers=start_workers,
    )
    if slab_delay > 0.0:
        _install_slab_delay(gate, float(slab_delay))
    gate.register(DRILL_TENANT, A, kmax=4)
    summary = gate.recover()
    return gate, (A, b, xe, x0), summary


def _install_slab_delay(gate, delay: float) -> None:
    """Chain onto the registry's page-in hook: every service built for
    a tenant sleeps ``delay`` inside `_block_solve` — the drill's
    window for landing SIGKILL mid-slab."""
    prev = gate.registry.on_page_in

    def hook(name, tenant):
        if prev is not None:
            prev(name, tenant)
        svc = tenant.svc
        if svc is None or getattr(svc, "_padur_delayed", False):
            return
        orig = svc._block_solve

        def slow_block_solve(*args, **kwargs):
            time.sleep(delay)
            return orig(*args, **kwargs)

        svc._block_solve = slow_block_solve
        svc._padur_delayed = True

    gate.registry.on_page_in = hook


def _drill_rhs(n, i):
    import numpy as np

    rng = np.random.default_rng(4000 + i)
    return rng.standard_normal(n)


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def cmd_serve(args) -> int:
    from partitionedarrays_jl_tpu.frontdoor import (
        serve_gate,
        serve_until_signalled,
    )

    gate, _sys, summary = build_drill_gate(
        args.journal_dir, checkpoint_dir=args.checkpoint_dir,
        shed_depth=args.shed_depth, slab_delay=args.slab_delay,
    )
    srv = serve_gate(gate, host=args.host, port=args.port)
    if args.url_file:
        tmp = args.url_file + ".tmp"
        with open(tmp, "w") as f:
            f.write(srv.url)
        os.replace(tmp, args.url_file)
    print(
        f"padur: serving {DRILL_TENANT} at {srv.url} "
        f"(journal={args.journal_dir}, recovered={summary})",
        flush=True,
    )
    rc = serve_until_signalled(srv, drain=args.drain)
    ckpt = gate.registry._tenants[DRILL_TENANT]
    print(
        "padur: shutdown "
        f"({'drain' if args.drain else 'checkpoint'}) rc={rc} "
        f"pending={ckpt.svc.pending() if ckpt.svc else 0}",
        flush=True,
    )
    return rc


# ---------------------------------------------------------------------------
# --check: the tier-1 smoke
# ---------------------------------------------------------------------------


def _check() -> int:
    import numpy as np

    import partitionedarrays_jl_tpu as pa
    from partitionedarrays_jl_tpu import telemetry
    from partitionedarrays_jl_tpu.frontdoor import (
        Gate,
        JournalCorruptError,
        RequestJournal,
        read_journal,
    )
    from partitionedarrays_jl_tpu.models import (
        assemble_poisson,
        cg,
        gather_pvector,
    )

    failures = []

    def expect(cond, msg):
        if not cond:
            failures.append(msg)

    root = tempfile.mkdtemp(prefix="padur-check-")

    # -- leg 1: journal round trip + fsync'd rotation -------------------
    jd = os.path.join(root, "unit")
    j = RequestJournal(jd, fsync=True, segment_bytes=4096)
    for i in range(40):
        j.append("shed", tag=f"r{i}", slo_class="besteffort", depth=i)
    segs = j.segments()
    expect(len(segs) >= 2, f"rotation must produce >1 segment ({segs})")
    j.close()
    j2 = RequestJournal(jd, fsync=False)
    sheds = [r for r in j2.prior_records if r["kind"] == "shed"]
    expect(len(sheds) == 40, f"replay must return all 40 ({len(sheds)})")
    expect(
        [r["tag"] for r in sheds] == [f"r{i}" for i in range(40)],
        "replay must preserve append order",
    )
    expect(j2.epoch == 2, f"epoch must increment per open ({j2.epoch})")
    seqs = [r["seq"] for r in j2.prior_records]
    expect(seqs == sorted(seqs), "seq must be monotonic across segments")
    j2.close()

    # -- leg 2: forced torn tail -> truncate + typed event --------------
    trunc0 = telemetry.counter("journal.truncated")
    ev0 = telemetry.counter("events.journal_truncated")
    last = sorted(j2.segments())[-1]
    with open(last, "ab") as f:
        f.write(b'{"kind":"completed","seq":999,"torn')  # no crc, torn
    j3 = RequestJournal(jd, fsync=False)
    expect(
        len([r for r in j3.prior_records if r["kind"] == "shed"]) == 40,
        "torn tail must not eat clean records",
    )
    expect(
        telemetry.counter("journal.truncated") == trunc0 + 1,
        "torn tail must bump journal.truncated",
    )
    expect(
        telemetry.counter("events.journal_truncated") == ev0 + 1,
        "torn tail must emit journal_truncated",
    )
    j3.close()

    # -- leg 3: mid-file corruption refuses typed -----------------------
    jc = os.path.join(root, "corrupt")
    jx = RequestJournal(jc, fsync=False)
    jx.append("shed", tag="a", slo_class="x", depth=0)
    jx.append("shed", tag="b", slo_class="x", depth=1)
    jx.close()
    seg = sorted(jx.segments())[0]
    data = bytearray(open(seg, "rb").read())
    data[data.find(b'"tag":"a"') + 8] = ord("z")  # flip a byte mid-file
    open(seg, "wb").write(bytes(data))
    try:
        read_journal(jc, strict=True)
        expect(False, "mid-file corruption must raise JournalCorruptError")
    except JournalCorruptError:
        pass

    # -- leg 4: gate journal round trip + idempotency across a crash ----
    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (8, 8))
        x_solo, _ = cg(A, b, x0=x0, tol=1e-9)
        gd = os.path.join(root, "gate")
        g1 = Gate(journal_dir=gd)
        g1.register("t", A, kmax=4)
        h1 = g1.submit("t", b, x0=x0, tol=1e-9, tag="done-req",
                       idempotency_key="check-key")
        g1.drain()
        x1 = gather_pvector(h1.result()[0])
        hq = g1.submit("t", b, x0=x0, tol=1e-9, tag="queued-req")
        # crash: no shutdown — g1 is simply abandoned
        adm0 = telemetry.counter("service.admitted")
        g2 = Gate(journal_dir=gd)
        g2.register("t", A, kmax=4)
        s = g2.recover()
        expect(
            s["completed"] == 1 and s["requeued"] == 1,
            f"recovery summary wrong: {s}",
        )
        hr = g2.handle(h1.rid)
        expect(hr is not None and hr.state == "done",
               "completed request must be servable from the journal")
        expect(
            np.array_equal(hr.result()[0], x1),
            "recovered result must be BITWISE the original",
        )
        # idempotent replay across the restart: original id, original
        # result, NO new admission
        h1b = g2.submit("t", b, idempotency_key="check-key")
        expect(h1b is hr, "idempotency key must return the original")
        expect(
            telemetry.counter("service.admitted") == adm0,
            "an idempotent replay must not admit a second solve",
        )
        g2.drain()
        xq, iq = g2.handle(hq.rid).result()
        expect(iq["converged"], "requeued request must complete")
        expect(
            np.array_equal(gather_pvector(xq), gather_pvector(x_solo)),
            "requeued request must complete bitwise-equal to solo",
        )
        return True

    expect(pa.prun(driver, pa.sequential, (2, 2)), "driver failed")

    for f in failures:
        print(f"padur --check FAILURE: {f}", file=sys.stderr)
    print("padur --check:", "FAILED" if failures else "OK")
    return 1 if failures else 0


# ---------------------------------------------------------------------------
# --drill: the SIGKILL crash drill (slow)
# ---------------------------------------------------------------------------


def _wait_for(predicate, timeout_s, what):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        v = predicate()
        if v:
            return v
        time.sleep(0.05)
    raise TimeoutError(f"padur drill: timed out waiting for {what}")


def _spawn_server(journal_dir, ckpt_dir, url_file, slab_delay):
    if os.path.exists(url_file):
        os.unlink(url_file)
    # a host-only drill: the server subprocess is pinned to the CPU
    # platform on purpose — the parent may hold the chip, which belongs
    # to one process, and kill -9 durability does not depend on the device
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PA_GATE_JOURNAL_FSYNC="1",
               # patx: spans persist next to the journal so the drill
               # reconstructs ONE stitched trace across the SIGKILL
               # (PA_TX pinned on — the drill asserts trace ids, so an
               # operator env with PA_TX=0 must not fail it spuriously)
               PA_TX="1",
               PA_TX_DIR=os.path.join(journal_dir, "tx"))
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "serve",
         "--journal-dir", journal_dir, "--checkpoint-dir", ckpt_dir,
         "--port", "0", "--url-file", url_file,
         "--slab-delay", str(slab_delay)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )

    def url_ready():
        if proc.poll() is not None:
            out = proc.stdout.read()
            raise RuntimeError(f"padur serve died at startup:\n{out}")
        return os.path.exists(url_file) and open(url_file).read()

    url = _wait_for(url_ready, 90.0, "server url")
    return proc, url


def _post(url, payload):
    import urllib.error
    import urllib.request

    req = urllib.request.Request(
        url + "/v1/solve", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _poll(url, rid, timeout_s=120.0):
    import urllib.request

    def terminal():
        with urllib.request.urlopen(
            f"{url}/v1/solve/{rid}", timeout=30
        ) as resp:
            poll = json.loads(resp.read())
        return (
            poll
            if poll["state"] not in ("gate-queued", "queued", "running")
            else None
        )

    return _wait_for(terminal, timeout_s, f"request {rid}")


def _drill(slab_delay: float = 0.5, n_requests: int = 4) -> int:
    """SIGKILL the serving gate mid-slab over HTTP, restart against the
    same journal + checkpoint dir, and assert: every admitted request
    completes bitwise-equal to its solo solve or fails typed — zero
    lost, zero duplicated (the idempotency-key resubmit returns the
    original result)."""
    import numpy as np

    import partitionedarrays_jl_tpu as pa
    from partitionedarrays_jl_tpu.frontdoor import read_journal
    from partitionedarrays_jl_tpu.models import (
        assemble_poisson,
        cg,
        gather_pvector,
        scatter_pvector_values,
    )

    failures = []

    def expect(cond, msg):
        if not cond:
            failures.append(msg)

    from partitionedarrays_jl_tpu.telemetry import tracing

    root = tempfile.mkdtemp(prefix="padur-drill-")
    jd = os.path.join(root, "journal")
    cd = os.path.join(root, "ckpt")
    uf = os.path.join(root, "url")

    # the oracle: each request's SOLO solve, in-process (deadline-free
    # requests run unchunked, so the served block solve per column IS
    # the solo trajectory — bitwise)
    def oracle(parts):
        A, b, xe, x0 = assemble_poisson(parts, DRILL_GRID)
        n = A.rows.ngids
        out = []
        for i in range(n_requests):
            bg = _drill_rhs(n, i)
            bv = scatter_pvector_values(
                np.asarray(bg, dtype=np.float64), A.cols
            )
            x, info = cg(A, bv, tol=1e-9)
            out.append((bg, gather_pvector(x), info["iterations"]))
        return out

    solo = pa.prun(oracle, pa.sequential, (2, 2))

    print(f"padur drill: starting server (journal={jd})", flush=True)
    proc, url = _spawn_server(jd, cd, uf, slab_delay)
    ids = []
    traces = {}  # rid -> trace_id acknowledged pre-crash
    try:
        for i in range(n_requests):
            status, payload = _post(url, {
                "tenant": DRILL_TENANT,
                "b": [float(v) for v in solo[i][0]],
                "tol": 1e-9,
                "tag": f"drill-{i}",
                "idempotency_key": f"drill-key-{i}",
            })
            expect(status == 202, f"submit {i} must 202 (got {status})")
            ids.append(payload["id"])
            expect(
                bool(payload.get("trace_id")),
                f"submit {i} must acknowledge a trace_id",
            )
            traces[payload["id"]] = payload.get("trace_id")
        # land the kill MID-SLAB: wait for a dispatch to be journaled
        # (the slab is then sleeping inside _block_solve), then -9
        _wait_for(
            lambda: any(
                r.get("kind") == "dispatched"
                for r in read_journal(jd)
            ),
            60.0, "a dispatched record",
        )
        time.sleep(slab_delay / 4)  # into the slab's sleep window
        proc.send_signal(signal.SIGKILL)
        proc.wait()
        print("padur drill: SIGKILL delivered mid-slab", flush=True)
    except BaseException:
        proc.kill()
        proc.wait()
        raise

    completed_before = sum(
        1 for r in read_journal(jd) if r.get("kind") == "completed"
    )
    expect(
        completed_before < n_requests,
        "the kill must land before every request completed "
        f"(completed={completed_before}) — raise --slab-delay",
    )

    # restart on the same journal; no slab delay (finish fast)
    proc2, url2 = _spawn_server(jd, cd, uf, 0.0)
    try:
        results = {}
        for i, rid in enumerate(ids):
            poll = _poll(url2, rid)
            results[rid] = poll
            expect(
                poll["state"] in ("done", "failed"),
                f"{rid}: must reach a terminal state ({poll['state']})",
            )
            expect(
                poll.get("trace_id") == traces[rid],
                f"{rid}: the recovered request must keep its ORIGINAL "
                f"trace_id ({traces[rid]} -> {poll.get('trace_id')})",
            )
            if poll["state"] == "done":
                expect(
                    poll["x"] == [float(v) for v in solo[i][1]],
                    f"{rid}: recovered result must be BITWISE the solo "
                    "solve",
                )
                expect(
                    poll["info"]["iterations"] == solo[i][2]
                    or poll["info"].get("recovered", False),
                    f"{rid}: iteration count must match solo",
                )
            else:
                expect(
                    bool(poll.get("error")),
                    f"{rid}: a failure must be TYPED ({poll})",
                )
        done = sum(
            1 for p in results.values() if p["state"] == "done"
        )
        print(
            f"padur drill: {done}/{n_requests} done, "
            f"{n_requests - done} typed-failed, 0 lost", flush=True,
        )
        # zero duplicated: the idempotency-key resubmit returns the
        # ORIGINAL id + result, and the journal holds exactly one
        # completed record per rid
        status, payload = _post(url2, {
            "tenant": DRILL_TENANT,
            "b": [float(v) for v in solo[0][0]],
            "tol": 1e-9,
            "idempotency_key": "drill-key-0",
        })
        expect(
            payload.get("id") == ids[0] and payload.get("replayed"),
            f"idempotent resubmit must return the original id "
            f"({payload})",
        )
        poll = _poll(url2, ids[0])
        expect(
            poll["state"] == "done"
            and poll["x"] == [float(v) for v in solo[0][1]],
            "idempotent resubmit must serve the original bitwise result",
        )
        # graceful shutdown: the SIGTERM exit-code contract
        proc2.send_signal(signal.SIGTERM)
        rc2 = proc2.wait(timeout=60)
        expect(rc2 == 0, f"SIGTERM shutdown must exit 0 (got {rc2})")
    except BaseException:
        proc2.kill()
        proc2.wait()
        raise

    recs = read_journal(jd)
    per_rid = {}
    for r in recs:
        if r.get("kind") == "completed":
            per_rid[r["rid"]] = per_rid.get(r["rid"], 0) + 1
    expect(
        all(c == 1 for c in per_rid.values()),
        f"zero duplicated: one completed record per rid ({per_rid})",
    )
    terminal = {
        r["rid"] for r in recs if r.get("kind") in ("completed", "failed")
    }
    expect(
        set(ids) <= terminal,
        f"zero lost: every admitted id must reach a terminal record "
        f"(missing: {set(ids) - terminal})",
    )

    # -- patx: ONE stitched trace per admitted request ------------------
    spans = tracing.load_spans(os.path.join(jd, "tx"))
    interrupted_total = 0
    for rid in ids:
        tid = traces[rid]
        mine = [s for s in spans if s.get("trace_id") == tid]
        expect(mine, f"{rid}: no spans persisted for trace {tid}")
        for p in tracing.verify_trace(spans, tid):
            expect(False, f"{rid}: {p}")  # incl. ZERO orphan spans
        tids = {s["trace_id"] for s in mine}
        expect(
            tids == {tid},
            f"{rid}: the crash must not fork the trace ({tids})",
        )
        interrupted = [
            s for s in mine if s.get("status") == "interrupted"
        ]
        interrupted_total += len(interrupted)
        # a request the kill caught mid-flight stitches: its post-crash
        # root span parents to the (interrupted) pre-crash root
        stitched = [
            s for s in mine
            if s["kind"] == "rpc.request" and s.get("attrs", {}).get(
                "recovered"
            )
        ]
        for s in stitched:
            expect(
                s.get("parent_id") in {m["span_id"] for m in mine},
                f"{rid}: recovered root must parent to the pre-crash "
                "root span",
            )
    expect(
        interrupted_total >= 1,
        "the SIGKILL must leave at least one interrupted span "
        "(something was mid-flight)",
    )
    print(
        f"padur drill: {len(ids)} stitched traces, "
        f"{interrupted_total} interrupted spans, 0 orphans",
        flush=True,
    )

    for f in failures:
        print(f"padur --drill FAILURE: {f}", file=sys.stderr)
    print("padur --drill:", "FAILED" if failures else "OK")
    return 1 if failures else 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true",
                    help="in-process smoke: journal round-trip, torn "
                         "tail, gate recovery + idempotency")
    ap.add_argument("--drill", action="store_true",
                    help="SIGKILL crash drill over HTTP (subprocess)")
    ap.add_argument("--slab-delay", type=float, default=0.5,
                    help="drill: per-slab sleep widening the kill "
                         "window (serve: injected into _block_solve)")
    sub = ap.add_subparsers(dest="cmd")
    ps = sub.add_parser("serve", help="serve the drill tenant")
    ps.add_argument("--journal-dir", required=True)
    ps.add_argument("--checkpoint-dir", default=None)
    ps.add_argument("--host", default="127.0.0.1")
    ps.add_argument("--port", type=int, default=0)
    ps.add_argument("--url-file", default=None)
    ps.add_argument("--slab-delay", type=float, default=0.0)
    ps.add_argument("--shed-depth", type=int, default=4096)
    ps.add_argument("--drain", action="store_true",
                    help="drain on SIGTERM instead of checkpointing")
    args = ap.parse_args(argv)

    if args.check:
        return _check()
    if args.drill:
        return _drill(slab_delay=args.slab_delay)
    if args.cmd == "serve":
        return cmd_serve(args)
    ap.print_help()
    return 2


if __name__ == "__main__":
    sys.exit(main())
