#!/usr/bin/env python
"""pafleet — the replicated gate fleet console and failover drill.

One gate process is a service; a FLEET of them is a service that
survives losing one. `frontdoor.fleet` supplies the mechanics
(rendezvous tenant routing, CRC'd lease heartbeats, journal adoption,
shed-forward peer picking); this tool runs them:

* ``serve --fleet-dir D --replica g0``  one replica process: its own
  port, journal dir (``D/g0``), pamon registry, lease heartbeat, and
  peer watcher; publishes ``D/g0/url`` + ``D/g0/pid`` atomically.
* ``kill --fleet-dir D --replica g0``   SIGKILL a replica by pid file
  (the drill's murder weapon, available to operators too).
* ``route --fleet-dir D TENANT``        print the replica that owns a
  tenant (rendezvous rank; residency stays warm there).
* ``--check``   tier-1 smoke, in-process: two replicas on ephemeral
  ports -> deterministic routing -> shed-forward 307 (solved on the
  peer, same client trace) -> simulated lease-missed failover (the
  survivor adopts the dead replica's journal; its requests finish
  under their original ids) -> torn-lease typed refusal; event trail
  and metric deltas asserted both ways.
* ``--drill``   the real thing (``-m slow``): N serve subprocesses,
  open-loop load, ``kill -9`` of one replica mid-load, then assert
  ZERO admitted requests lost or duplicated (bitwise-equal-to-solo or
  typed; idempotent resubmit returns the original id), ONE stitched
  trace across the replica hop, and report per-class SLO attainment
  from the survivor.

Usage:
    python tools/pafleet.py --check
    python tools/pafleet.py --drill
    python tools/pafleet.py serve --fleet-dir /tmp/fleet --replica g0
    python tools/pafleet.py route --fleet-dir /tmp/fleet poisson8
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


def _pagate():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "pagate", os.path.join(REPO, "tools", "pagate.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# serve / kill / route
# ---------------------------------------------------------------------------


def cmd_serve(args) -> int:
    from partitionedarrays_jl_tpu.frontdoor import (
        FleetMap,
        FleetMember,
        serve_gate,
        serve_until_signalled,
    )

    fleet_dir = os.path.abspath(args.fleet_dir)
    fm = FleetMap(fleet_dir)
    jd = fm.journal_dir(args.replica)
    os.makedirs(jd, exist_ok=True)
    # one shared span dir: patx stitches forwards/failovers into ONE
    # trace only when every replica persists spans to the same place
    os.environ.setdefault("PA_TX_DIR", os.path.join(fleet_dir, "tx"))
    pagate = _pagate()
    gate, _systems = pagate.build_demo_gate(
        budget=args.budget, shed_watermark=args.shed_depth,
        journal_dir=jd, rid_namespace=args.replica,
    )
    srv = serve_gate(gate, host=args.host, port=args.port)
    member = FleetMember(
        fleet_dir, args.replica, gate, server=srv,
        lease_s=args.lease_s,
    )
    srv.peer_picker = member.pick_peer
    member.start()
    with open(os.path.join(jd, "pid.tmp"), "w") as f:
        f.write(str(os.getpid()))
    os.replace(os.path.join(jd, "pid.tmp"), os.path.join(jd, "pid"))
    fm.write_url(args.replica, srv.url)  # url last: readiness signal
    print(
        f"pafleet: replica {args.replica} at {srv.url} "
        f"(journal={jd}, lease_s={member.lease_s})",
        flush=True,
    )
    rc = serve_until_signalled(srv, drain=args.drain)
    member.stop()
    print(f"pafleet: replica {args.replica} shutdown rc={rc}",
          flush=True)
    return rc


def cmd_kill(args) -> int:
    pid_path = os.path.join(
        os.path.abspath(args.fleet_dir), args.replica, "pid"
    )
    with open(pid_path) as f:
        pid = int(f.read().strip())
    os.kill(pid, signal.SIGKILL)
    print(f"pafleet: SIGKILL -> replica {args.replica} (pid {pid})")
    return 0


def cmd_route(args) -> int:
    from partitionedarrays_jl_tpu.frontdoor import FleetMap, route

    fm = FleetMap(os.path.abspath(args.fleet_dir))
    replicas = fm.replicas()
    if not replicas:
        print("pafleet route: no replicas in fleet dir",
              file=sys.stderr)
        return 1
    r = route(args.tenant, replicas)
    print(f"{args.tenant} -> {r} ({fm.url(r) or 'no url yet'})")
    return 0


# ---------------------------------------------------------------------------
# --check: the tier-1 smoke (in-process, ephemeral ports)
# ---------------------------------------------------------------------------


def _check() -> int:
    import urllib.error
    import urllib.request

    from partitionedarrays_jl_tpu import telemetry
    from partitionedarrays_jl_tpu.frontdoor import (
        FleetMember,
        LeaseCorruptError,
        http_solve,
        rendezvous_rank,
        route,
        serve_gate,
    )
    from partitionedarrays_jl_tpu.telemetry import tracing

    failures = []

    def expect(cond, msg):
        if not cond:
            failures.append(msg)

    reg = telemetry.registry()

    def counters():
        snap = reg.snapshot()["counters"]
        return {
            k: snap.get(k, 0)
            for k in (
                "fleet.forwarded", "fleet.lease_missed",
                "fleet.adopted{outcome=requeued}",
            )
        }

    ev0 = {
        k: telemetry.counter(f"events.{k}")
        for k in ("fleet_forwarded", "fleet_lease_missed",
                  "fleet_adopted", "request_adopted")
    }
    c0 = counters()

    # -- leg 1: routing is deterministic and movement-minimal ----------
    reps = ["g0", "g1", "g2"]
    for t in ("poisson8", "poisson12", "alpha", "beta"):
        expect(route(t, reps) == route(t, reps),
               f"route({t}) must be deterministic")
        expect(route(t, reps) in reps, f"route({t}) must pick a replica")
        grown = route(t, reps + ["g3"])
        expect(grown == route(t, reps) or grown == "g3",
               f"adding a replica may only move {t} TO the new one")
    expect(
        rendezvous_rank("poisson8", reps)[0] == route("poisson8", reps),
        "route must be rank[0]",
    )

    fleet_dir = tempfile.mkdtemp(prefix="pafleet-check-")
    pagate = _pagate()
    # replica g0: tiny watermark (sheds at depth 2); g1: headroom
    gA, systems = pagate.build_demo_gate(
        budget="all", shed_watermark=2,
        journal_dir=os.path.join(fleet_dir, "g0"), rid_namespace="g0",
    )
    gB, _ = pagate.build_demo_gate(
        budget="all", shed_watermark=8,
        journal_dir=os.path.join(fleet_dir, "g1"), rid_namespace="g1",
    )
    srvA = serve_gate(gA, port=0)
    srvB = serve_gate(gB, port=0)
    memberA = FleetMember(fleet_dir, "g0", gA, server=srvA,
                          lease_s=0.2)
    memberB = FleetMember(fleet_dir, "g1", gB, server=srvB,
                          lease_s=0.2)
    srvA.peer_picker = memberA.pick_peer
    srvB.peer_picker = memberB.pick_peer
    memberA.map.write_url("g0", srvA.url)
    memberB.map.write_url("g1", srvB.url)
    memberA.heartbeat()
    memberB.heartbeat()
    b, x0 = pagate._demo_rhs(systems, "poisson8")
    a_alive = True
    try:
        # -- leg 2: shed-forward -----------------------------------------
        # hold g0 paused with an interactive backlog at its watermark,
        # then submit besteffort THROUGH the client: g0 sheds, finds
        # g1's headroom via lease+healthz, and 307-forwards; the client
        # follows and the solve lands on g1 under the SAME trace
        gA.paused = True
        backlog = []
        for i in range(2):
            out = urllib.request.urlopen(urllib.request.Request(
                srvA.url + "/v1/solve",
                data=json.dumps({
                    "tenant": "poisson8", "b": list(map(float, b)),
                    "tol": 1e-9, "slo_class": "interactive",
                    "tag": f"fleet-backlog-{i}",
                    "idempotency_key": f"fleet-bk-{i}",
                }).encode(),
                headers={"Content-Type": "application/json"},
                method="POST",
            ))
            backlog.append(json.loads(out.read())["id"])
        memberB.heartbeat()  # keep g1's lease fresh for the picker
        tp = tracing.mint_trace()
        fwd = http_solve(
            srvA.url, "poisson8", b, tol=1e-9,
            slo_class="besteffort", tag="fleet-forward",
            idempotency_key="fleet-fwd", traceparent=tp.traceparent(),
            timeout_s=300.0,
        )
        expect(fwd.get("state") == "done",
               f"forwarded solve must finish on the peer ({fwd})")
        expect(str(fwd.get("id", "")).startswith("g1-"),
               f"forward must land on g1 (rid {fwd.get('id')})")
        expect(fwd.get("trace_id") == tp.trace_id,
               "the forwarded hop must stay in the client's trace "
               f"({tp.trace_id} -> {fwd.get('trace_id')})")
        # no peer with headroom -> the 429 contract is unchanged
        os.unlink(os.path.join(fleet_dir, "g1", "lease.json"))
        try:
            urllib.request.urlopen(urllib.request.Request(
                srvA.url + "/v1/solve",
                data=json.dumps({
                    "tenant": "poisson8", "b": list(map(float, b)),
                    "slo_class": "besteffort", "tag": "fleet-shed",
                }).encode(),
                headers={"Content-Type": "application/json"},
                method="POST",
            ))
            expect(False, "shed without a live peer must be 429")
        except urllib.error.HTTPError as e:
            expect(e.code == 429,
                   f"shed without a live peer must 429 (got {e.code})")
            expect("Retry-After" in dict(e.headers),
                   "the 429 must keep its Retry-After")
        memberB.heartbeat()  # restore g1's lease

        # -- leg 3: lease-missed failover --------------------------------
        # g0 "dies" with its interactive backlog still queued: stop its
        # server (checkpoint shutdown, the journal survives), let its
        # lease go stale, and run g1's sweep — g1 must adopt, requeue,
        # and finish the backlog under the ORIGINAL ids
        srvA.stop(drain=False)
        a_alive = False
        deadline = time.time() + 5.0
        while time.time() < deadline:
            lease = memberB.map.lease("g0") or {}
            if time.time() - float(lease.get("wall", 0)) \
                    > 3.0 * memberB.lease_s:
                break
            time.sleep(0.05)
        adopted = memberB.check_peers()
        expect("g0" in adopted,
               f"g1 must adopt the stale-leased g0 ({adopted})")
        expect(adopted.get("g0", {}).get("requeued", 0) >= 2,
               f"the backlog must requeue on g1 ({adopted})")
        expect(memberB.check_peers() == {},
               "a second sweep must be a no-op (per-dir idempotence)")
        for rid in backlog:
            poll = None
            t0 = time.monotonic()
            while time.monotonic() - t0 < 240.0:
                with urllib.request.urlopen(
                    f"{srvB.url}/v1/solve/{rid}"
                ) as resp:
                    poll = json.loads(resp.read())
                if poll["state"] not in ("gate-queued", "queued",
                                         "running"):
                    break
                time.sleep(0.01)
            expect(poll and poll["state"] == "done",
                   f"adopted {rid} must finish on g1 "
                   f"({poll and poll['state']})")
        # idempotent across the hop: the pre-death key returns the
        # original (g0-minted) id from the SURVIVOR
        from partitionedarrays_jl_tpu.frontdoor.rpc import _vector

        rep = {}
        h = gB.submit(
            "poisson8", b=_vector(gB, "poisson8", b, "float64"),
            tag="fleet-backlog-0", idempotency_key="fleet-bk-0",
            replay_out=rep,
        )
        expect(h.rid == backlog[0] and rep.get("replayed"),
               f"idempotent resubmit must return the original id "
               f"({h.rid} vs {backlog[0]})")

        # -- leg 4: torn lease refuses, never a false takeover -----------
        g2 = os.path.join(fleet_dir, "g2")
        os.makedirs(g2, exist_ok=True)
        with open(os.path.join(g2, "lease.json"), "w") as f:
            f.write('{"replica": "g2", "wall": 1.0, "cr')  # torn
        try:
            memberB.check_peers()
            expect(False, "a torn lease must raise LeaseCorruptError")
        except LeaseCorruptError:
            pass
        expect(
            "g2" not in memberB._missed
            and not any(
                n.startswith("journal-") for n in os.listdir(g2)
            ),
            "a torn lease must NOT trigger adoption",
        )
    finally:
        if a_alive:
            srvA.stop(drain=False)
        srvB.stop(drain=False)
    c1 = counters()
    d = {k: c1[k] - c0[k] for k in c0}
    expect(d["fleet.forwarded"] == 1,
           f"exactly one shed-forward must count ({d})")
    expect(d["fleet.lease_missed"] == 1,
           f"exactly one lease miss must count ({d})")
    expect(d["fleet.adopted{outcome=requeued}"] >= 2,
           f"the adopted backlog must count per outcome ({d})")
    for k, v0 in ev0.items():
        expect(telemetry.counter(f"events.{k}") > v0,
               f"event {k} must fire")
    for f in failures:
        print(f"pafleet --check FAILURE: {f}", file=sys.stderr)
    print("pafleet --check:", "FAILED" if failures else "OK")
    return 1 if failures else 0


# ---------------------------------------------------------------------------
# --drill: kill -9 one replica mid-load (slow)
# ---------------------------------------------------------------------------


def _wait_for(predicate, timeout_s, what):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        v = predicate()
        if v:
            return v
        time.sleep(0.05)
    raise TimeoutError(f"pafleet drill: timed out waiting for {what}")


def _spawn_replica(fleet_dir, replica, lease_s):
    # a host-only drill: the replicas are pinned to the CPU platform on
    # purpose — a chip belongs to one process, so N serve subprocesses
    # can never share one, and what the drill proves (routing, leases,
    # journal failover) does not depend on the device
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PA_GATE_JOURNAL_FSYNC="1", PA_TX="1",
               PA_TX_DIR=os.path.join(fleet_dir, "tx"),
               PA_FLEET_LEASE_S=str(lease_s))
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "serve",
         "--fleet-dir", fleet_dir, "--replica", replica,
         "--port", "0", "--budget", "all", "--shed-depth", "4096"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    url_path = os.path.join(fleet_dir, replica, "url")

    def ready():
        if proc.poll() is not None:
            out = proc.stdout.read()
            raise RuntimeError(
                f"pafleet serve {replica} died at startup:\n{out}"
            )
        return os.path.exists(url_path) and open(url_path).read()

    url = _wait_for(ready, 180.0, f"{replica} url")
    return proc, url.strip()


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


def _poll(url, rid, timeout_s=240.0):
    import urllib.error
    import urllib.request

    def terminal():
        try:
            with urllib.request.urlopen(
                f"{url}/v1/solve/{rid}", timeout=30
            ) as resp:
                poll = json.loads(resp.read())
        except urllib.error.HTTPError:
            return None  # not adopted yet
        return (
            poll
            if poll["state"] not in ("gate-queued", "queued", "running")
            else None
        )

    return _wait_for(terminal, timeout_s, f"request {rid}")


def _drill(n_requests: int = 6, lease_s: float = 0.5) -> int:
    """Kill -9 one replica of a live fleet mid-load; the survivor must
    adopt its journal and finish every admitted request — zero lost,
    zero duplicated, one stitched trace per request."""
    import numpy as np

    import partitionedarrays_jl_tpu as pa
    from partitionedarrays_jl_tpu.frontdoor import (
        read_journal,
        route,
    )
    from partitionedarrays_jl_tpu.models import (
        assemble_poisson,
        cg,
        gather_pvector,
        scatter_pvector_values,
    )
    from partitionedarrays_jl_tpu.telemetry import tracing

    failures = []

    def expect(cond, msg):
        if not cond:
            failures.append(msg)

    fleet_dir = tempfile.mkdtemp(prefix="pafleet-drill-")
    replicas = ["g0", "g1"]
    tenant = "poisson12"
    victim = route(tenant, replicas)
    survivor = next(r for r in replicas if r != victim)

    def _rhs(n, i):
        rng = np.random.default_rng(7000 + i)
        return rng.standard_normal(n)

    # the oracle: each request's solo solve, in-process, bitwise
    def oracle(parts):
        A, b, xe, x0 = assemble_poisson(parts, (12, 12))
        n = A.rows.ngids
        out = []
        for i in range(n_requests):
            bg = _rhs(n, i)
            bv = scatter_pvector_values(
                np.asarray(bg, dtype=np.float64), A.cols
            )
            x, info = cg(A, bv, tol=1e-9)
            out.append((bg, gather_pvector(x), info["iterations"]))
        return out

    solo = pa.prun(oracle, pa.sequential, (2, 2))

    print(
        f"pafleet drill: fleet={replicas} victim={victim} "
        f"(owns {tenant}) survivor={survivor}", flush=True,
    )
    procs = {}
    urls = {}
    try:
        for r in replicas:
            procs[r], urls[r] = _spawn_replica(fleet_dir, r, lease_s)
        # open-loop arrival at the ROUTED replica: fire the whole
        # burst without waiting for completions (interactive on the
        # victim; one batch on the survivor keeps it busy too)
        ids, traces = [], {}
        for i in range(n_requests):
            status, payload = _post(urls[victim], {
                "tenant": tenant,
                "b": [float(v) for v in solo[i][0]],
                "tol": 1e-9, "slo_class": "interactive",
                "tag": f"fleet-drill-{i}",
                "idempotency_key": f"fleet-drill-key-{i}",
            })
            expect(status == 202,
                   f"submit {i} must 202 (got {status})")
            ids.append(payload["id"])
            traces[payload["id"]] = payload.get("trace_id")
        _post(urls[survivor], {
            "tenant": "poisson8",
            "b": [1.0] * 64, "slo_class": "batch",
            "tag": "fleet-drill-peer",
        })
        # kill MID-LOAD: once work is dispatched but before the burst
        # drains
        jd = os.path.join(fleet_dir, victim)
        _wait_for(
            lambda: any(
                r.get("kind") == "dispatched"
                for r in read_journal(jd)
            ),
            120.0, "a dispatched record on the victim",
        )
        procs[victim].send_signal(signal.SIGKILL)
        procs[victim].wait()
        completed_before = sum(
            1 for r in read_journal(jd)
            if r.get("kind") == "completed"
        )
        expect(
            completed_before < n_requests,
            "the kill must land before the burst drained "
            f"(completed={completed_before}) — raise n_requests",
        )
        print(
            f"pafleet drill: SIGKILL -> {victim} "
            f"({completed_before}/{n_requests} completed)", flush=True,
        )
        # the survivor's watcher declares the lease missed and adopts;
        # every admitted id must reach a terminal state THERE
        results = {}
        for i, rid in enumerate(ids):
            poll = _poll(urls[survivor], rid)
            results[rid] = poll
            expect(
                poll["state"] in ("done", "failed"),
                f"{rid}: must reach a terminal state ({poll['state']})",
            )
            expect(
                poll.get("trace_id") == traces[rid],
                f"{rid}: adopted request must keep its ORIGINAL "
                f"trace_id ({traces[rid]} -> {poll.get('trace_id')})",
            )
            if poll["state"] == "done":
                expect(
                    poll["x"] == [float(v) for v in solo[i][1]],
                    f"{rid}: adopted result must be BITWISE the solo "
                    "solve",
                )
            else:
                expect(bool(poll.get("error")),
                       f"{rid}: a failure must be TYPED ({poll})")
        done = sum(
            1 for p in results.values() if p["state"] == "done"
        )
        print(
            f"pafleet drill: {done}/{n_requests} done, "
            f"{n_requests - done} typed-failed, 0 lost", flush=True,
        )
        # zero duplicated: idempotent resubmit against the survivor
        # returns the victim-minted id and its bitwise result
        status, payload = _post(urls[survivor], {
            "tenant": tenant,
            "b": [float(v) for v in solo[0][0]],
            "tol": 1e-9,
            "idempotency_key": "fleet-drill-key-0",
        })
        expect(
            payload.get("id") == ids[0] and payload.get("replayed"),
            f"idempotent resubmit must return the original id "
            f"({payload})",
        )
        # per-class SLO attainment, reported from the survivor
        import urllib.request

        with urllib.request.urlopen(
            urls[survivor] + "/metrics.json", timeout=30
        ) as resp:
            snap = json.loads(resp.read())["counters"]
        for cls in ("interactive", "batch", "besteffort"):
            req = snap.get(
                f"gate.slo.requests{{slo_class={cls}}}", 0
            )
            hit = snap.get(f"gate.slo.hits{{slo_class={cls}}}", 0)
            att = (hit / req) if req else None
            print(
                f"pafleet drill: SLO {cls:12s} "
                f"{hit}/{req} attainment="
                f"{'n/a' if att is None else f'{att:.3f}'}",
                flush=True,
            )
        # graceful survivor shutdown: the exit-code contract holds
        procs[survivor].send_signal(signal.SIGTERM)
        rc = procs[survivor].wait(timeout=120)
        expect(rc == 0, f"survivor SIGTERM must exit 0 (got {rc})")
    except BaseException:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
                p.wait()
        raise

    # journal union: every admitted id terminal exactly once
    recs = read_journal(jd) + read_journal(
        os.path.join(fleet_dir, survivor)
    )
    per_rid = {}
    for r in recs:
        if r.get("kind") == "completed":
            per_rid[r["rid"]] = per_rid.get(r["rid"], 0) + 1
    expect(
        all(c == 1 for c in per_rid.values()),
        f"zero duplicated: one completed record per rid ({per_rid})",
    )
    terminal = {
        r["rid"] for r in recs
        if r.get("kind") in ("completed", "failed", "adopted")
    }
    expect(
        set(ids) <= terminal,
        f"zero lost: every admitted id must reach a terminal or "
        f"adopted record (missing: {set(ids) - terminal})",
    )

    # patx: ONE stitched trace across the replica hop
    spans = tracing.load_spans(os.path.join(fleet_dir, "tx"))
    hops = 0
    for rid in ids:
        tid = traces[rid]
        mine = [s for s in spans if s.get("trace_id") == tid]
        expect(mine, f"{rid}: no spans persisted for trace {tid}")
        for p in tracing.verify_trace(spans, tid):
            expect(False, f"{rid}: {p}")
        adopted_roots = [
            s for s in mine
            if s["kind"] == "rpc.request"
            and s.get("attrs", {}).get("adopted_from")
        ]
        hops += len(adopted_roots)
        for s in adopted_roots:
            expect(
                s.get("parent_id") in {m["span_id"] for m in mine},
                f"{rid}: the adopted root must parent to the victim's "
                "root span — one tree across the hop",
            )
    expect(hops >= 1,
           "at least one request must have hopped replicas")
    print(
        f"pafleet drill: {len(ids)} stitched traces, "
        f"{hops} replica hops, 0 orphans", flush=True,
    )
    for f in failures:
        print(f"pafleet --drill FAILURE: {f}", file=sys.stderr)
    print("pafleet --drill:", "FAILED" if failures else "OK")
    return 1 if failures else 0


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true",
                    help="tier-1 in-process fleet smoke")
    ap.add_argument("--drill", action="store_true",
                    help="kill -9 failover drill (slow; subprocesses)")
    sub = ap.add_subparsers(dest="cmd")
    ps = sub.add_parser("serve", help="run one fleet replica")
    ps.add_argument("--fleet-dir", required=True)
    ps.add_argument("--replica", required=True)
    ps.add_argument("--host", default="127.0.0.1")
    ps.add_argument("--port", type=int, default=0)
    ps.add_argument("--budget", default="all")
    ps.add_argument("--shed-depth", type=int, default=32)
    ps.add_argument("--lease-s", type=float, default=None)
    ps.add_argument("--drain", action="store_true")
    pk = sub.add_parser("kill", help="SIGKILL a replica by pid file")
    pk.add_argument("--fleet-dir", required=True)
    pk.add_argument("--replica", required=True)
    pr = sub.add_parser("route", help="print a tenant's replica")
    pr.add_argument("--fleet-dir", required=True)
    pr.add_argument("tenant")
    args = ap.parse_args(argv)

    if args.check:
        return _check()
    if args.drill:
        return _drill()
    if args.cmd == "serve":
        return cmd_serve(args)
    if args.cmd == "kill":
        return cmd_kill(args)
    if args.cmd == "route":
        return cmd_route(args)
    ap.print_help()
    return 2


if __name__ == "__main__":
    sys.exit(main())
