#!/usr/bin/env python
"""Seeded chaos harness — composed fault schedules over real gRPC, judged
against a fault-free oracle chain (ISSUE 12; docs/RESILIENCE.md).

Two drivers, both importable by tests (tests/test_faults.py runs a
tier-1-sized schedule) and runnable standalone (``make chaos``):

``run_chaos`` — the composed-schedule run: one CHAOS server constructed
under a KT_FAULTS schedule (8 fault kinds on one seed: transport
UNAVAILABLE + reset, mid-step and mid-commit exceptions, injected step
latency, a session-table wipe, a TTL clock jump, spool corruption and
truncation) and one ORACLE server with the null plane, both behind real
gRPC on unix sockets.  A seeded churn chain drives the chaos session; the
driver mirrors every perturbation onto the oracle session with the SAME
recovery structure (a chaos re-establish is mirrored as an oracle
re-establish of the identical pod list, so both chains see identical
request sequences and the deterministic solver must answer identically).
After every recovered step the global invariants hold:

1. **No silent divergence** — the chaos client's merged view is
   byte-identical to the chaos server's live chain entry.
2. **Oracle parity** — the chaos view equals the fault-free oracle view
   as a node partition (per-node offering + pod set; node NAMES come from
   a process-global counter and can never match across servers).
3. **Typed errors only** — everything raised through the facade is
   SolveShedError / SolveDeadlineError / SolveRetriesExhausted /
   SolveStepFailed.
4. **Bounded recovery** — full re-establishes <= faults injected + 1
   (the +1 is the initial establishment): one fault costs AT MOST one
   full solve, never a retry storm.

``run_restart`` — the kill-and-restart scenario: a solver sidecar
SUBPROCESS serving a churn chain is SIGTERM'd mid-chain and relaunched on
the same unix socket.  With KT_SESSION_DIR the replacement restores the
session spool and every client's next delta is served WARM (zero
re-establishing full solves); without it, exactly N clients pay exactly
one re-establish each.  ``make chaos`` asserts the zero / exactly-N
re-solve counts; the restore latency is unmeasured.

``run_fleet`` — the fleet-failover scenarios (ISSUE 13): N solver
replicas on unix sockets sharing ONE session spool, fleet-aware clients
(``FleetClient`` session-affinity routing), every chain mirrored onto a
fault-free single-replica oracle.  Modes:

- ``kill``      — hard-kill one of N mid-chain (no snapshot, no lease
  release); after the lease TTL the surviving replicas STEAL the dead
  replica's sessions from the shared spool and serve their next delta
  WARM: zero re-establishing solves, byte-parity vs the oracle.
- ``drain``     — graceful drain of one of N: establishments refused with
  the DRAINING hint, served deltas hand their chains off (record + lease
  release + drop), clients proactively re-home; zero re-establishes.
- ``kill-cold`` — the no-spool baseline: the kill costs exactly ONE
  re-establish per orphaned session (the PR-10 floor).
- ``contend``   — two surviving replicas adopt the SAME dead session
  concurrently: exactly one wins the lease, the loser refuses typed.
- ``stale``     — the spool is rolled back to pre-kill records (a PVC
  restore adversary): adoption succeeds but the epoch check refuses to
  serve the stale chain — exactly one re-establish per session, never a
  silent divergence.

``tests/test_fleet.py`` runs kill and drain (0 re-establishes each) in
tier-1; ``make chaos-fleet`` runs every mode.

Usage::

    python scripts/chaos_drive.py                      # composed schedule
    python scripts/chaos_drive.py --steps 120 --pods 5000 --seed 7
    python scripts/chaos_drive.py --restart            # kill + restart
    python scripts/chaos_drive.py --restart --no-snapshot
    python scripts/chaos_drive.py --fleet              # kill-one-of-three
    python scripts/chaos_drive.py --fleet --mode drain --seed 24
"""

from __future__ import annotations

import argparse
import os
import random
import signal
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

TYPED_ERRORS_DOC = ("SolveShedError", "SolveDeadlineError",
                    "SolveRetriesExhausted", "SolveStepFailed")


def make_pods(n, tag):
    """Unconstrained steady-state churn pods (the warm-start shape:
    6 deployment families, no topology)."""
    from karpenter_tpu.models.pod import PodSpec

    out = []
    for i in range(n):
        g = i % 6
        out.append(PodSpec(
            name=f"{tag}-{i}", labels={"app": f"ws{g}"},
            requests={"cpu": 0.25 * (1 + g % 3),
                      "memory": (0.5 + g % 4) * 2**30},
            owner_key=f"ws{g}",
        ))
    return out


def canonical(res):
    """Server-independent view of a solution: the node partition (offering
    + sorted pod names per node) + the infeasible set.  Node NAMES come
    from a process-global counter, so cross-server comparison must be
    name-blind."""
    return (
        sorted((n.instance_type, n.zone, n.capacity_type,
                tuple(sorted(p.name for p in n.pods)))
               for n in res.nodes),
        dict(res.infeasible),
    )


def default_schedule(seed: int, steps: int) -> str:
    """8 fault kinds composed on ONE seeded schedule, spread over the
    chain so recoveries interleave (occurrence numbers are per-site:
    transport counts client RPC attempts, session_table counts table
    get/put, delta_step counts applied steps, snapshot_write counts spool
    writes)."""
    mid = max(6, steps // 2)
    late = max(10, (3 * steps) // 4)
    return (
        f"seed={seed};"
        # ride-through: one injected UNAVAILABLE, retried transparently
        f"rpc_unavailable@transport:at=4;"
        # exhaustion: two consecutive attempts fail -> typed give-up
        f"rpc_reset@transport:at=9;rpc_unavailable@transport:at=10;"
        # mid-step + half-mutated commit exceptions -> eviction + typed
        f"dispatch_exc@delta_step:at=6;"
        f"dispatch_exc@delta_commit:at={mid};"
        # injected latency while in_step=True
        f"slow_step@delta_step:at=3:value=0.02;"
        # the table adversaries: wipe + TTL clock jump
        f"session_wipe@session_table:at={mid + 2};"
        f"clock_jump@session_table:at={late}:value=100000;"
        # the spool adversaries (detected at the next restore)
        f"snapshot_corrupt@snapshot_write:at=1;"
        f"snapshot_truncate@snapshot_write:at=3:value=0.4"
    )


def _serve_pair(tmp, pods_n, schedule, session_dir=None, snapshot_s=None):
    """(oracle, chaos) in-process servers on unix sockets.  Construction
    ORDER is the env dance: the oracle stack is built with KT_FAULTS
    unset (null plane), then the chaos stack under the schedule."""
    from karpenter_tpu.metrics import Registry
    from karpenter_tpu.service.server import SolverService, make_server
    from karpenter_tpu.solver.scheduler import BatchScheduler

    def build(sock):
        reg = Registry()
        sched = BatchScheduler(backend="oracle", registry=reg)
        service = SolverService(sched, registry=reg)
        # construct the pipeline EAGERLY: components capture their fault
        # plane (and session spool) from env at construction, and the
        # service builds pipelines lazily on first RPC — by which time
        # this harness has restored the environment
        service._pipeline_for(sched)
        srv, _ = make_server(service, host=sock)
        return reg, service, srv

    assert not os.environ.get("KT_FAULTS"), \
        "run the harness from a KT_FAULTS-clean environment"
    o_sock = f"unix:{tmp}/oracle.sock"
    c_sock = f"unix:{tmp}/chaos.sock"
    oracle = build(o_sock)
    saved = {}
    try:
        saved["KT_FAULTS"] = os.environ.pop("KT_FAULTS", None)
        os.environ["KT_FAULTS"] = schedule
        if session_dir is not None:
            saved["KT_SESSION_DIR"] = os.environ.pop("KT_SESSION_DIR", None)
            os.environ["KT_SESSION_DIR"] = session_dir
        if snapshot_s is not None:
            saved["KT_SESSION_SNAPSHOT_S"] = os.environ.pop(
                "KT_SESSION_SNAPSHOT_S", None)
            os.environ["KT_SESSION_SNAPSHOT_S"] = str(snapshot_s)
        chaos = build(c_sock)
    finally:
        for key, old in saved.items():
            if old is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = old
    return (oracle, o_sock), (chaos, c_sock)


def run_chaos(seed=42, steps=60, pods_n=1500, churn=6, schedule=None,
              verbose=True):
    """The composed-schedule chaos run.  Returns the scoreboard dict;
    raises AssertionError the moment an invariant breaks."""
    from karpenter_tpu.admission import SolveDeadlineError, SolveShedError
    from karpenter_tpu.metrics import FAULTS_INJECTED, registry as global_reg
    from karpenter_tpu.models.catalog import generate_catalog
    from karpenter_tpu.models.provisioner import Provisioner
    from karpenter_tpu.service.client import (
        DeltaSession, SolveRetriesExhausted, SolveStepFailed, SolverClient,
    )

    schedule = schedule or default_schedule(seed, steps)
    catalog = generate_catalog(full=False)
    provs = [Provisioner(name="default").with_defaults()]
    tmp = tempfile.mkdtemp(prefix="kt-chaos-")
    spool = os.path.join(tmp, "spool")
    (oracle, o_sock), (chaos, c_sock) = _serve_pair(
        tmp, pods_n, schedule, session_dir=spool, snapshot_s=0.0001)
    (o_reg, o_service, o_srv) = oracle
    (c_reg, c_service, c_srv) = chaos
    typed = {k: 0 for k in TYPED_ERRORS_DOC}

    def injected_total():
        # server-side sites count into the chaos server's registry;
        # client-side (transport) into the process default — sum both,
        # as a delta against the harness's start
        return (sum(c_reg.counter(FAULTS_INJECTED).values.values())
                + sum(global_reg.counter(FAULTS_INJECTED).values.values()))

    injected_base = injected_total()
    try:
        # chaos client: ride-through retry with a fast test backoff; it is
        # built AFTER the env dance above restored KT_FAULTS="" — the
        # TRANSPORT faults come from the schedule captured by... no: the
        # client plane must see the schedule, so set it for this ctor
        os.environ["KT_FAULTS"] = schedule
        try:
            c_client = SolverClient(c_sock, timeout=120.0, retries=1,
                                    backoff_s=0.01)
        finally:
            os.environ.pop("KT_FAULTS", None)
        sess = DeltaSession(c_sock, timeout=120.0, client=c_client)
        o_sess = DeltaSession(o_sock, timeout=120.0)
        pods = make_pods(pods_n, "cw")
        sess.solve(list(pods), provs, catalog)
        o_sess.solve(list(pods), provs, catalog)
        rng = random.Random(seed)
        live = [p.name for p in pods]
        cum_add, cum_rm = [], []
        last_resends = sess.full_resends
        checked = 0
        for k in range(steps):
            rm = rng.sample(live, churn)
            rms = set(rm)
            live = [n for n in live if n not in rms]
            add = make_pods(churn, f"cw{k}")
            live += [p.name for p in add]
            try:
                cur = sess.solve_delta(added=add, removed=rm)
            except (SolveShedError, SolveDeadlineError,
                    SolveRetriesExhausted, SolveStepFailed) as err:
                typed[type(err).__name__] += 1
                cum_add += add
                cum_rm += rm
                continue
            # ktlint-free zone (scripts): any OTHER exception is an
            # invariant breach and propagates — errors must be typed
            if sess.full_resends > last_resends:
                # the chaos call re-established internally (eviction,
                # wipe, clock jump, mid-step failure on a prior call):
                # mirror the SAME full solve onto the oracle — identical
                # pod list, identical order
                o_sess.solve(sess.pods(), provs, catalog)
                last_resends = sess.full_resends
            else:
                o_sess.solve_delta(added=cum_add + add, removed=cum_rm + rm)
            cum_add, cum_rm = [], []
            # invariant 1: client view == server chain, byte-identical
            pipe = list(c_service._pipelines.values())[0]
            with pipe._delta_tab._lock:   # direct peek: get() would
                entry = pipe._delta_tab._sessions.get(sess.session_id)
            if entry is not None:         # advance the fault schedule
                assert entry.prev.assignments == cur.assignments, \
                    f"step {k}: client assignments diverged from chain"
                assert entry.prev.infeasible == cur.infeasible, \
                    f"step {k}: client infeasible diverged from chain"
                assert ({n.name: sorted(p.name for p in n.pods)
                         for n in entry.prev.nodes}
                        == {n.name: sorted(p.name for p in n.pods)
                            for n in cur.nodes}), \
                    f"step {k}: client node map diverged from chain"
            # invariant 2: fault-free oracle parity (name-blind partition)
            assert canonical(cur) == canonical(o_sess.result()), \
                f"step {k}: chaos view diverged from the fault-free oracle"
            checked += 1
        injected = injected_total() - injected_base
        # invariant 4: bounded recovery — one fault costs at most one
        # full re-establishing solve
        assert sess.full_resends - 1 <= injected, (
            f"{sess.full_resends - 1} re-establishes for {injected} "
            "injected faults — recovery is not bounded")
        board = {
            "seed": seed, "steps": steps, "pods": pods_n,
            "parity_checked_steps": checked,
            "typed_errors": typed,
            "full_resends": sess.full_resends,
            "delta_rpcs": sess.delta_rpcs,
            "faults_injected": int(injected),
            "injected_by_rule": {
                f"{dict(lk).get('kind')}@{dict(lk).get('site')}": v
                for reg in (c_reg, global_reg)
                for lk, v in reg.counter(FAULTS_INJECTED).values.items()
                if v},
        }
        if verbose:
            print("chaos run clean:")
            for key, val in board.items():
                print(f"  {key}: {val}")
        return board
    finally:
        o_srv.stop(grace=None)
        c_srv.stop(grace=None)
        o_service.close()
        c_service.close()


# ---- kill-and-restart scenario (subprocess server) ----------------------

_SERVE_ARGS = ["-m", "karpenter_tpu.service.server", "--backend", "oracle"]


def _spawn_server(sock, session_dir, snapshot_s="2"):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("KT_FAULTS", None)
    if session_dir:
        env["KT_SESSION_DIR"] = session_dir
        env["KT_SESSION_SNAPSHOT_S"] = snapshot_s
    else:
        env.pop("KT_SESSION_DIR", None)
    proc = subprocess.Popen(
        [sys.executable, *_SERVE_ARGS, "--host", sock],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    return proc


def _wait_ready(sock, timeout=60.0):
    from karpenter_tpu.service.client import SolverClient

    deadline = time.time() + timeout
    last = None
    while time.time() < deadline:
        client = SolverClient(sock, timeout=5.0, retries=0)
        try:
            if client.health(timeout=2.0).ok:
                client.close()
                return
        except Exception as err:  # noqa: BLE001 — startup polling
            last = err
            client.reset()
            time.sleep(0.25)
        finally:
            client.close()
    raise RuntimeError(f"server on {sock} never became healthy: {last}")


def run_restart(pods_n=4000, clients=4, pre_steps=4, post_steps=4, churn=6,
                seed=11, snapshot=True, verbose=True):
    """SIGTERM a serving subprocess mid-chain, relaunch it on the same
    socket, continue every client's chain.  Returns the scoreboard:
    ``extra_resends`` is 0 with a snapshot (every session restored warm)
    and exactly ``clients`` without one."""
    from karpenter_tpu.models.catalog import generate_catalog
    from karpenter_tpu.models.provisioner import Provisioner
    from karpenter_tpu.service.client import DeltaSession, SolverClient

    catalog = generate_catalog(full=False)
    provs = [Provisioner(name="default").with_defaults()]
    tmp = tempfile.mkdtemp(prefix="kt-restart-")
    sock = f"unix:{tmp}/solver.sock"
    spool = os.path.join(tmp, "spool") if snapshot else ""
    proc = _spawn_server(sock, spool)
    sessions, rngs, lives = [], [], []
    try:
        _wait_ready(sock)
        per = pods_n // clients
        for c in range(clients):
            client = SolverClient(sock, timeout=120.0, retries=2,
                                  backoff_s=0.3)
            s = DeltaSession(sock, timeout=120.0, client=client)
            pods = make_pods(per, f"rc{c}")
            s.solve(list(pods), provs, catalog)
            sessions.append(s)
            rngs.append(random.Random(seed + c))
            lives.append([p.name for p in pods])

        def step(c, tag):
            rm = rngs[c].sample(lives[c], churn)
            rms = set(rm)
            lives[c] = [n for n in lives[c] if n not in rms]
            add = make_pods(churn, f"rc{c}{tag}")
            lives[c] += [p.name for p in add]
            return sessions[c].solve_delta(added=add, removed=rm)

        for k in range(pre_steps):
            for c in range(clients):
                step(c, f"a{k}")
        resends_before = [s.full_resends for s in sessions]
        # SIGTERM: graceful — the serve handler drains + snapshots
        t_kill = time.perf_counter()
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=30)
        proc2 = _spawn_server(sock, spool)
        _wait_ready(sock)
        restart_wall_s = time.perf_counter() - t_kill
        # continue every chain through the restarted replica: the retry
        # budget rides through any residual connection raciness
        t0 = time.perf_counter()
        first_delta_ms = []
        for c in range(clients):
            t1 = time.perf_counter()
            step(c, "post0")
            first_delta_ms.append((time.perf_counter() - t1) * 1000.0)
        for k in range(1, post_steps):
            for c in range(clients):
                step(c, f"b{k}")
        post_wall_s = time.perf_counter() - t0
        extra = sum(s.full_resends for s in sessions) - sum(resends_before)
        board = {
            "snapshot": snapshot,
            "clients": clients,
            "pods": pods_n,
            "extra_resends": extra,
            "restart_wall_s": round(restart_wall_s, 2),
            "first_post_delta_ms": [round(v, 2) for v in first_delta_ms],
            "post_chain_wall_s": round(post_wall_s, 2),
        }
        if verbose:
            print(f"restart run ({'with' if snapshot else 'WITHOUT'} "
                  "snapshot):")
            for key, val in board.items():
                print(f"  {key}: {val}")
        expect = 0 if snapshot else clients
        assert extra == expect, (
            f"expected {expect} post-restart re-establishes, saw "
            f"{extra}")
        return board
    finally:
        for s in sessions:
            try:
                s.close()
            except Exception:  # noqa: BLE001 — teardown
                pass
        for p in (proc, locals().get("proc2")):
            if p is not None and p.poll() is None:
                p.kill()
                p.wait(timeout=10)


# ---- fleet-failover scenarios (ISSUE 13) ---------------------------------

def _build_replica(sock, spool, replica, lease_s, snapshot_s):
    """One in-process solver replica with its fault/spool config captured
    from env at construction (the _serve_pair env dance)."""
    from karpenter_tpu.metrics import Registry
    from karpenter_tpu.service.server import SolverService, make_server
    from karpenter_tpu.solver.scheduler import BatchScheduler

    saved = {}
    env = {"KT_REPLICA_ID": replica}
    if spool:
        env["KT_SESSION_DIR"] = spool
        env["KT_SESSION_SNAPSHOT_S"] = str(snapshot_s)
        env["KT_SESSION_LEASE_S"] = str(lease_s)
    try:
        for key, val in env.items():
            saved[key] = os.environ.pop(key, None)
            os.environ[key] = val
        if not spool:
            saved["KT_SESSION_DIR"] = os.environ.pop("KT_SESSION_DIR", None)
        reg = Registry()
        sched = BatchScheduler(backend="oracle", registry=reg)
        service = SolverService(sched, registry=reg)
        pipe = service._pipeline_for(sched)
        srv, _ = make_server(service, host=sock)
    finally:
        for key, old in saved.items():
            if old is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = old
    return {"reg": reg, "service": service, "pipe": pipe, "srv": srv,
            "sock": sock, "replica": replica, "alive": True}


def _hard_kill(rep):
    """The unclean death: the gRPC server stops answering and the
    dispatcher (and with it the periodic snapshot + lease renewal) halts
    — no final spool write, no lease release.  The replica's sessions
    become adoptable only after the lease TTL, exactly like a crashed
    pod on a shared PVC."""
    rep["srv"].stop(grace=None)
    rep["pipe"]._stop.set()
    rep["pipe"]._thread.join(timeout=10)
    rep["alive"] = False


def _settle_spool(reps, deadline_s=10.0):
    """Wait until every live session's spool record is at its chain's
    committed epoch (the periodic writer runs on idle ticks; a HARD kill
    right after a step may lose the last write — bounded by design, but
    the warm-failover scenarios measure the steady state, where the
    record IS current)."""
    from karpenter_tpu.service import snapshot as snap

    deadline = time.time() + deadline_s
    while time.time() < deadline:
        behind = 0
        for rep in reps:
            if not rep["alive"]:
                continue
            tab = rep["pipe"]._delta_tab
            spool = rep["pipe"]._spool_dir
            if tab is None or not spool:
                continue
            with tab._lock:
                live = {sid: e.epoch for sid, e in tab._sessions.items()}
            for sid, epoch in live.items():
                blob = snap.read_record(spool, sid)
                if blob is None:
                    behind += 1
                    continue
                try:
                    raw, _ = snap.unpack(blob)
                    if int(snap.unpack_entry(raw[0])["epoch"]) != epoch:
                        behind += 1
                except snap.SnapshotRefused:
                    behind += 1
        if behind == 0:
            return
        time.sleep(0.05)
    raise RuntimeError("session spool never settled to the live epochs")


def run_fleet(replicas=3, clients=6, pods_n=1200, pre_steps=3, post_steps=3,
              churn=4, seed=23, mode="kill", lease_s=0.4, verbose=True):
    """One fleet-failover scenario (see the module docstring's mode
    catalog).  Returns the scoreboard; raises AssertionError the moment
    an invariant breaks."""
    import threading

    from karpenter_tpu.admission import SolveDeadlineError, SolveShedError
    from karpenter_tpu.metrics import SESSION_ADOPTIONS
    from karpenter_tpu.models.catalog import generate_catalog
    from karpenter_tpu.models.provisioner import Provisioner
    from karpenter_tpu.service.client import (
        DeltaSession, FleetClient, SolveRetriesExhausted, SolveStepFailed,
        SolverDraining,
    )
    from karpenter_tpu.service import snapshot as snap
    from karpenter_tpu.analysis import conformance
    from karpenter_tpu.obs import protocol

    assert mode in ("kill", "drain", "kill-cold", "contend", "stale"), mode
    spooled = mode != "kill-cold"
    catalog = generate_catalog(full=False)
    provs = [Provisioner(name="default").with_defaults()]
    tmp = tempfile.mkdtemp(prefix="kt-fleet-")
    spool = os.path.join(tmp, "spool") if spooled else ""
    reps = [_build_replica(f"unix:{tmp}/r{i}.sock", spool, f"replica-{i}",
                           lease_s, 0.0001) for i in range(replicas)]
    oracle = _build_replica(f"unix:{tmp}/oracle.sock", "", "oracle", 1.0, 0)
    socks = [r["sock"] for r in reps]
    typed = {k: 0 for k in
             TYPED_ERRORS_DOC + ("SolverDraining", "LeaseHeld")}
    sessions = []
    # conformance tap (ISSUE 17): every replica is in-process, so one
    # process-global recorder sees the whole fleet's protocol
    # transitions; the checker asserts each session's observed sequence
    # is a path of the model-checked automaton
    rec = protocol.TransitionRecorder()
    prev_sink = protocol.installed()
    protocol.install(rec)
    try:
        rng = random.Random(seed)
        per = max(20, pods_n // clients)
        for c in range(clients):
            fc = FleetClient(socks, timeout=120.0, retries=1,
                             backoff_s=0.02)
            sess = DeltaSession(socks[0], timeout=120.0, client=fc)
            mirror = DeltaSession(oracle["sock"], timeout=120.0)
            pods = make_pods(per, f"fl{c}")
            sess.solve(list(pods), provs, catalog)
            mirror.solve(list(pods), provs, catalog)
            sessions.append({
                "fc": fc, "sess": sess, "mirror": mirror,
                "live": [p.name for p in pods],
                "cum_add": [], "cum_rm": [],
                "resends": sess.full_resends,
            })

        def step(s, tag):
            """One churn step + oracle mirror + parity check.  Returns
            False when the step surfaced a typed error (perturbation
            stays pending, cumulative retry next call)."""
            rm = rng.sample(s["live"], min(churn, len(s["live"])))
            rms = set(rm)
            s["live"] = [n for n in s["live"] if n not in rms]
            add = make_pods(churn, tag)
            s["live"] += [p.name for p in add]
            try:
                cur = s["sess"].solve_delta(added=add, removed=rm)
            except (SolveShedError, SolveDeadlineError,
                    SolveRetriesExhausted, SolveStepFailed,
                    SolverDraining) as err:
                typed[type(err).__name__] += 1
                s["cum_add"] += add
                s["cum_rm"] += rm
                return False
            if s["sess"].full_resends > s["resends"]:
                # the chain re-established internally: mirror the SAME
                # full solve so both sides see identical sequences
                s["mirror"].solve(s["sess"].pods(), provs,
                                  catalog)
                s["resends"] = s["sess"].full_resends
            else:
                s["mirror"].solve_delta(added=s["cum_add"] + add,
                                        removed=s["cum_rm"] + rm)
            s["cum_add"], s["cum_rm"] = [], []
            assert canonical(cur) == canonical(s["mirror"].result()), \
                f"{tag}: fleet view diverged from the fault-free oracle"
            return True

        for k in range(pre_steps):
            for c, s in enumerate(sessions):
                step(s, f"fl{c}a{k}")
        if spooled:
            _settle_spool(reps)
        # the victim: the replica serving the most sessions (rendezvous
        # picks it deterministically per seed via the session ids)
        by_ep = {r["sock"]: [] for r in reps}
        for s in sessions:
            by_ep[s["fc"].endpoint_for(s["sess"].session_id)].append(s)
        victim = max(reps, key=lambda r: len(by_ep[r["sock"]]))
        victim_sessions = by_ep[victim["sock"]]
        n_victim = len(victim_sessions)
        resends_before = sum(s["sess"].full_resends for s in sessions)

        contended = {}
        if mode in ("kill", "kill-cold", "contend", "stale"):
            if mode == "stale":
                # snapshot the CURRENT records (file-by-file: survivors
                # are live writers, so temp files come and go under any
                # tree walk), then advance the chains so the on-disk
                # state we roll back to is genuinely stale.  The pipeline
                # namespaces its spool per backend ("oracle" here).
                import shutil

                rec_dir = os.path.join(spool, "oracle",
                                       snap.SESSIONS_SUBDIR)
                stale_dir = os.path.join(tmp, "stale-copy")
                os.makedirs(stale_dir, exist_ok=True)
                for name in os.listdir(rec_dir):
                    if not name.endswith(snap.RECORD_SUFFIX):
                        continue
                    try:
                        shutil.copyfile(os.path.join(rec_dir, name),
                                        os.path.join(stale_dir, name))
                    except FileNotFoundError:
                        pass  # consumed/replaced mid-copy
                for k in range(2):
                    for c, s in enumerate(sessions):
                        step(s, f"fl{c}s{k}")
                _settle_spool(reps)
            _hard_kill(victim)
            if mode == "stale":
                # roll the RECORDS back in place (the PVC-restore
                # adversary): every record is now at a PRE-advance epoch.
                # Surviving replicas are live writers on this tree, so
                # records are replaced file-by-file (their own sessions'
                # next periodic write re-freshens them) — never an rmtree
                # under a live writer.
                for name in os.listdir(stale_dir):
                    t = os.path.join(rec_dir, name + ".stale-tmp")
                    shutil.copyfile(os.path.join(stale_dir, name), t)
                    os.replace(t, os.path.join(rec_dir, name))
            if spooled:
                # leases stop renewing at death; adoption is legal (as a
                # counted STEAL) only after the TTL — the fleet's
                # failover-warmness window
                time.sleep(lease_s + 0.3)
        elif mode == "drain":
            victim["service"].drain()

        if mode == "contend":
            # two survivors race to adopt the SAME dead session directly
            # (the client would only ever ask one): exactly one may win
            survivors = [r for r in reps if r["alive"]][:2]
            sid = victim_sessions[0]["sess"].session_id \
                if victim_sessions else sessions[0]["sess"].session_id
            results = {}
            barrier = threading.Barrier(len(survivors))

            def adopt(rep):
                barrier.wait()
                tab = rep["pipe"]._delta_tab
                results[rep["replica"]] = tab.adopt(
                    rep["pipe"]._spool_dir, sid)

            threads = [threading.Thread(target=adopt, args=(r,))
                       for r in survivors]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            winners = [k for k, v in results.items() if v is not None]
            assert len(winners) == 1, (
                f"lease contention yielded {len(winners)} adopters "
                f"(want exactly 1): {results}")
            held = sum(r["reg"].counter(SESSION_ADOPTIONS).get(
                {"outcome": "lease_held"}) for r in reps)
            assert held >= 1.0, "the losing adopter was not counted"
            typed["LeaseHeld"] += int(held)

        # continue every chain through the fleet
        post_ok = 0
        for k in range(post_steps):
            for c, s in enumerate(sessions):
                if step(s, f"fl{c}b{k}"):
                    post_ok += 1
        extra = sum(s["sess"].full_resends for s in sessions) \
            - resends_before

        if spooled:
            # let zombie reconciliation land before the audit: a replica
            # holding a stale adopted entry drops it (lease_lost) on its
            # next periodic snapshot pass, after the establishment that
            # superseded it force-took the lease
            time.sleep(0.4)
        # single-owner audit: every session lives in AT MOST one serving
        # replica's table (the acceptance criterion: no seed may ever
        # yield two replicas serving the same session epoch)
        multi_owner = []
        for s in sessions:
            sid = s["sess"].session_id
            holders = []
            for rep in reps:
                if not rep["alive"]:
                    continue
                tab = rep["pipe"]._delta_tab
                with tab._lock:
                    if sid in tab._sessions:
                        holders.append(rep["replica"])
            if len(holders) > 1:
                multi_owner.append((sid, holders))
        assert not multi_owner, \
            f"sessions served by multiple replicas: {multi_owner}"

        adoptions = {}
        for rep in reps:
            for lk, v in rep["reg"].counter(
                    SESSION_ADOPTIONS).values.items():
                if v:
                    key = dict(lk).get("outcome", "")
                    adoptions[key] = adoptions.get(key, 0) + int(v)
        report = conformance.check_events(rec.events_by_session())
        board = {
            "mode": mode, "seed": seed, "replicas": replicas,
            "clients": clients, "pods": per * clients,
            "victim": victim["replica"],
            "victim_sessions": n_victim,
            "extra_resends": extra,
            "post_steps_served": post_ok,
            "typed_errors": {k: v for k, v in typed.items() if v},
            "adoptions": adoptions,
            "conformance": {"sessions": report.sessions,
                            "events": report.events,
                            "violations": len(report.violations)},
        }
        if verbose:
            print(f"fleet {mode} run clean:")
            for key, val in board.items():
                print(f"  {key}: {val}")
        assert report.ok, report.format()
        if mode in ("kill", "drain"):
            assert extra == 0, (
                f"{extra} re-establishing solve(s) on the warm "
                f"failover path (mode={mode}; want ZERO — the spool "
                "must hand every chain off warm)")
            if mode == "kill" and n_victim:
                stolen = adoptions.get("stolen", 0)
                assert stolen >= n_victim, (
                    f"only {stolen} steal-adoptions for {n_victim} "
                    "orphaned sessions")
        elif mode == "kill-cold":
            assert extra == n_victim, (
                f"{extra} re-establishes for {n_victim} orphaned "
                "sessions without a spool — the cold path must cost "
                "exactly one per session")
        elif mode == "contend":
            # at most ONE re-establish (only when the probe's winner
            # was not the endpoint the client routes to)
            assert extra <= 1, (
                f"{extra} re-establishes after one contended "
                "adoption — contention must cost at most one")
        elif mode == "stale":
            assert extra == n_victim, (
                f"{extra} re-establishes for {n_victim} stale-spool "
                "sessions — stale adoption must cost exactly one "
                "re-establish each, never serve the stale chain")
        return board
    finally:
        protocol.install(prev_sink)
        for rep in reps + [oracle]:
            try:
                rep["srv"].stop(grace=None)
                rep["service"].close()
            except Exception:  # noqa: BLE001 — teardown
                pass
        for s in sessions:
            try:
                s["sess"].close()
                s["mirror"].close()
            except Exception:  # noqa: BLE001 — teardown
                pass


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--pods", type=int, default=1500)
    ap.add_argument("--churn", type=int, default=6)
    ap.add_argument("--schedule", default=None,
                    help="override the composed KT_FAULTS schedule")
    ap.add_argument("--restart", action="store_true",
                    help="run the kill-and-restart scenario instead")
    ap.add_argument("--no-snapshot", action="store_true",
                    help="(--restart) run WITHOUT KT_SESSION_DIR: every "
                         "client pays one re-establish")
    ap.add_argument("--fleet", action="store_true",
                    help="run a fleet-failover scenario (N replicas, one "
                         "shared session spool, fleet-aware clients)")
    ap.add_argument("--mode", default="kill",
                    choices=["kill", "drain", "kill-cold", "contend",
                             "stale"],
                    help="(--fleet) scenario: hard kill-one-of-N (warm "
                         "steal), graceful drain-one-of-N, the no-spool "
                         "cold baseline, concurrent lease contention, or "
                         "stale-spool adoption")
    ap.add_argument("--replicas", type=int, default=3)
    args = ap.parse_args(argv)
    if args.fleet:
        run_fleet(replicas=args.replicas, seed=args.seed, mode=args.mode)
    elif args.restart:
        run_restart(snapshot=not args.no_snapshot)
    else:
        run_chaos(seed=args.seed, steps=args.steps, pods_n=args.pods,
                  churn=args.churn, schedule=args.schedule)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
