#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload W --seed N --seconds S --trace 0|1

This process is the OPERATOR side: pinned to the CPU before anything is
imported, it never touches the chip.  It starts one child, the solver sidecar
(``sidecar.py``), which alone holds the chip; generates the cell's traffic
from ``--seed`` (``gen.py``); warms the cell's own shapes with untimed
requests; drives a closed loop of one client with zero think time for
``--seconds`` seconds and on to the end of the pass it is in (every window
holds whole passes of the cell's pool or deck, every pass the same work;
``solve_ms`` and ``pods_per_s`` are taken over the whole window, and every
pass's wall, the median pass and the stalled passes are in the run's ``info``
lines: ``whole_passes``);
reads ``/metrics`` deltas, the device and — traced — the profiler trace from
the sidecar; stops the sidecar; compares every answer the client decoded with
the plain reference (``plainref.py``); checks its own last line against the
contract (``lastline.py``) and prints it.

Exit code 0 only with a last line printed.  No TPU, fewer chips than the cell
asks for, a sidecar that does not stop cleanly, a last line the contract
rejects: non-zero and no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: bounded waits, seconds
SIDECAR_READY_S = 300.0
SIDECAR_EXIT_S = 120.0
#: a traced run's window closes at the first pass boundary after this long
TRACE_MAX_S = 12.0
#: a pass this many times its window's median pass is reported as stalled
STALL_OVER = 1.25


class RunFailed(Exception):
    """The run cannot give a result; the message goes to stderr."""


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:7.2f}s] {msg}",
          file=sys.stderr, flush=True)


#: earlier lines of standard output, printed only with a result behind them
EARLIER: list = []


def info(what: str, **fields) -> None:
    """An earlier line of standard output (the last line is the result); a
    run that fails prints none of them there, only on standard error."""
    EARLIER.append(json.dumps({"info": what, **fields}))
    log(EARLIER[-1][:1000])


# ---------------------------------------------------------------------------
# the sidecar child
# ---------------------------------------------------------------------------


class Sidecar:
    def __init__(self, run_dir: str, platform: str, chips: int,
                 sidecar_env: dict) -> None:
        #: loopback TCP, both ports picked by the sidecar (``wait_ready``)
        self.target = self.metrics_url = ""
        self.log_path = os.path.join(run_dir, "sidecar.log")
        self.started = time.perf_counter()
        with open(self.log_path, "w") as logf:
            self.proc = subprocess.Popen(
                [sys.executable, os.path.join(HERE, "sidecar.py"),
                 "--platform", platform, "--chips", str(chips)],
                cwd=ROOT, env=sidecar_env, stdin=subprocess.PIPE,
                stdout=subprocess.PIPE, stderr=logf, text=True)
        self.hello: dict = {}

    def log_tail(self, n: int = 3000) -> str:
        try:
            with open(self.log_path) as f:
                return f.read()[-n:]
        except OSError:
            return ""

    def _read_json(self, what: str) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise RunFailed(f"sidecar closed its output while {what} "
                            f"(exit {self.proc.poll()}):\n{self.log_tail()}")
        doc = json.loads(line)
        if "error" in doc:
            raise RunFailed(f"sidecar failed {what}: {doc['error']}")
        return doc

    def wait_ready(self) -> dict:
        import select

        deadline = time.monotonic() + SIDECAR_READY_S
        while True:
            if self.proc.poll() is not None:
                raise RunFailed(f"sidecar exited {self.proc.returncode} "
                                f"before serving:\n{self.log_tail()}")
            ready, _, _ = select.select([self.proc.stdout], [], [], 0.5)
            if ready:
                self.hello = self._read_json("starting")
                self.target = f"127.0.0.1:{self.hello['port']}"
                self.metrics_url = (f"http://127.0.0.1:"
                                    f"{self.hello['obs_port']}/metrics")
                return self.hello
            if time.monotonic() > deadline:
                raise RunFailed(f"sidecar not serving after "
                                f"{SIDECAR_READY_S:.0f}s:\n{self.log_tail()}")

    def command(self, line: str) -> dict:
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()
        return self._read_json(line.split()[0])

    def stop(self) -> int:
        """SIGTERM, wait, return the exit code (kills after the bound)."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=SIDECAR_EXIT_S)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
                return -9
        for pipe in (self.proc.stdin, self.proc.stdout):
            try:
                pipe.close()
            except OSError:
                pass
        return self.proc.returncode


# ---------------------------------------------------------------------------
# per-layer metrics: a file each, a reader each, found by name
# ---------------------------------------------------------------------------


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_layer_metrics(bench: dict, workload: str, ctx: dict) -> dict:
    out = {}
    for decl in bench["per_layer"]:
        if "workloads" in decl and workload not in decl["workloads"]:
            continue
        with open(os.path.join(HERE, "metrics", f"{decl['name']}.json")) as f:
            spec = json.load(f)
        reader = _module(os.path.join(HERE, "readers",
                                      f"{spec['reader']}.py"),
                         f"reader_{spec['reader']}")
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            out[decl["name"]] = {"value": value, "unit": decl["unit"]}
    return out


def p95(xs: list) -> float:
    """Nearest-rank 95th percentile of all values."""
    s = sorted(xs)
    return s[max(0, math.ceil(0.95 * len(s)) - 1)]


# ---------------------------------------------------------------------------
# the window: whole passes
# ---------------------------------------------------------------------------


def drive(kind, limit: float, clock=time.perf_counter,
          passes: int = 1) -> dict:
    """The closed loop: requests of ``kind`` one after another until the
    first pass boundary (``kind.whole()``) at or after ``limit`` seconds
    that is the ``passes``-th or a later one.
    ``walls`` holds every request's wall, a failed one's too; ``cuts`` the
    number of requests sent when each pass ended."""
    walls, cuts, failed, pods_offered = [], [], 0, 0
    t_open = clock()
    while True:
        t0 = clock()
        try:
            pods_offered += kind.request()
        except Exception as err:  # noqa: BLE001 — a failed request counts
            failed += 1
            log(f"request failed: {err!r}")
        now = clock()
        walls.append(now - t0)
        if kind.whole():
            cuts.append(len(walls))
            if now - t_open >= limit and len(cuts) >= passes:
                break
    return {"walls": walls, "cuts": cuts, "failed": failed,
            "pods_offered": pods_offered, "window_s": now - t_open}


def whole_passes(window: dict) -> dict:
    """What a window :func:`drive` closed reads.  ``solve_ms`` and
    ``pods_per_s`` are taken over ALL of it: its wall over its requests, the
    pods it offered over its wall.  Beside them, for the run's ``info``
    lines and for no metric: the wall of every pass (the sum of its
    requests' walls; every pass is the same work), what the MEDIAN pass
    would read as ``solve_ms``, and how many passes stood more than
    ``STALL_OVER`` x over that median — a stall inside one request moves
    the mean and has to show in the run's output."""
    walls, cuts = window["walls"], window["cuts"]
    pass_s = [sum(walls[a:b]) for a, b in zip([0] + cuts, cuts)]
    mid = statistics.median(pass_s)
    return {"passes": len(pass_s), "pass_s": pass_s,
            "solve_ms": window["window_s"] / len(walls) * 1000.0,
            "pods_per_s": window["pods_offered"] / window["window_s"],
            "median_solve_ms": mid * len(pass_s) / len(walls) * 1000.0,
            "stalled_passes": sum(p > STALL_OVER * mid for p in pass_s)}


# ---------------------------------------------------------------------------
# the two traffic kinds
# ---------------------------------------------------------------------------


class Burst:
    """Each request one fresh ``Solve`` of the whole cluster.  A pass is the
    whole pool, once, in the seed's order."""

    def __init__(self, inputs, cfg, traffic, seed, scale) -> None:
        import random

        import gen

        n = int(traffic["pool"])
        self.pool = [gen.salted(c, seed)
                     for c in gen.burst_pool(cfg, n, 0, scale)]
        random.Random(seed).shuffle(self.pool)
        self.warm = [gen.salted(c, seed) for c in gen.burst_pool(
            cfg, int(traffic["warm_pool"]), n, scale)]
        self.inputs = inputs
        self.pool_pods = [inputs.pods(c.groups) for c in self.pool]
        self.warm_pods = [inputs.pods(c.groups) for c in self.warm]
        self.warm_max = int(traffic["warm_max"])
        self.sent = 0
        #: every answer of the window, with the pool index it answers
        self.answers: list = []
        info("generator", kind="burst", pool=len(self.pool),
             warm_pool=len(self.warm),
             pods_per_request=gen.pods_per_request(cfg, scale),
             distinct=len({c.key for c in self.pool + self.warm}),
             order=[c.key[0] for c in self.pool])

    def connect(self, run) -> None:
        from karpenter_tpu.service.client import RemoteScheduler

        self.run = run
        self.remote = run.tamper(RemoteScheduler(
            run.sidecar.target, timeout=600.0, registry=run.registry))

    def _solve(self, pods):
        return self.remote.solve(pods, self.inputs.provisioners,
                                 self.inputs.catalog)

    def warm_up(self) -> None:
        """Untimed requests from the warm pool until the device tier has
        served one and a further request shows no cold tier and no compile."""
        served = 0
        for k in range(self.warm_max):
            mark = self.run.mark()
            t0 = time.perf_counter()
            self._solve(self.warm_pods[k % len(self.warm_pods)])
            wall = time.perf_counter() - t0
            seen = self.run.since(mark)
            log(f"warm {k + 1}: {wall * 1000:.0f} ms, {seen}")
            if seen["quiet"] and set(seen["tiers"]) == {"tpu"} and served:
                info("warm_up", requests=k + 1)
                return
            served += 1 if seen["tiers"].get("tpu") else 0
            if seen["compiling"]:
                time.sleep(0.5)
        raise RunFailed(f"the device tier did not serve this cell's shape "
                        f"steadily within {self.warm_max} warm requests")

    def request(self) -> int:
        """One timed request; returns the pods it offered."""
        i = self.sent % len(self.pool)
        self.sent += 1
        self.answers.append((i, self._solve(self.pool_pods[i])))
        return self.pool[i].n_pods

    def whole(self) -> bool:
        """The window stands at the end of a pass."""
        return self.sent % len(self.pool) == 0

    def close(self) -> None:
        self.remote.close()

    def cases(self) -> list:
        from plainref import Answer

        return [(self.pool[i].groups, Answer.of_result(res))
                for i, res in self.answers]

    def report(self, walls: list) -> None:
        n = len(self.pool)
        info("pool", sent=self.sent, pool=n,
             request_ms=[w * 1000.0 for w in walls[:n]])


class Reconcile:
    """One session; each request one ``solve_delta`` step of the deck.  A
    pass is one whole deck, and every run's k-th pass is the same steps on
    the same cluster (``gen.Steps``): the configuration's, under this seed's
    names.

    What is compared is every PASS BOUNDARY: the client's merged view when a
    deck ends, copied as plain data, beside the generator's ledger at that
    moment.  The validator judges each.  ``cost_ratio`` is taken over the
    first ``cost_passes`` of them (the traffic file's), however many more a
    faster program's window holds; an untraced window runs on until it holds
    that many, so every run that reports the metric read it off the same
    boundaries.  The configuration's cost ceiling (``cost_ratio_max``) is
    held on those and on the LAST boundary, where drift has had the longest
    to grow (``gen.boundary_costs``).  After EVERY step the pods its answer
    holds infeasible are counted, and each such pod-step is ``unplaced``:
    the operator acts on every answer, not on the one the window closes
    on."""

    def __init__(self, inputs, cfg, traffic, seed, scale) -> None:
        import gen

        self.traffic, self.inputs = traffic, inputs
        self.steps = gen.Steps(cfg, traffic, seed, scale)
        #: the warm-up's steps run over a ledger of their own
        self.warm = gen.Steps(cfg, traffic, seed, scale, stream="warm")
        self.cluster = self.steps.cluster
        self.first = inputs.pods(self.cluster.groups)
        self.modes: dict = {}
        self.warm_max = int(traffic["warm_max"])
        self.cost_passes = int(traffic["cost_passes"])
        #: per boundary ``(ledger, view, steps since a scale_down)``
        self.boundaries: list = []
        #: per pass, the pod-steps its answers (the boundary's own apart:
        #: the validator counts those) held infeasible
        self.on_the_way = [0]
        #: the pods out now, and where the first of them were left out: the
        #: pass and step, the step's kind and the mode it was answered in
        self.out: set = set()
        self.left_out_at: list = []
        self.step_no = 0
        self.last = ("", "")
        self.between_s = 0.0
        info("generator", kind="reconcile", standing=self.cluster.n_pods,
             deck=len(self.steps.deck_def), cost_passes=self.cost_passes)

    def connect(self, run) -> None:
        from karpenter_tpu.service.client import DeltaSession

        class Session(DeltaSession):
            """Reads the mode the server answered in off the reply."""

            last_mode = ""

            def _rpc(self, req):
                resp = super()._rpc(req)
                self.last_mode = getattr(resp, "delta_mode", "") or ""
                return resp

        self.run = run
        self.sess = run.tamper(Session(run.sidecar.target, timeout=600.0,
                                       registry=run.registry))

    def _step(self, step: dict):
        added = self.inputs.pods(
            self.cluster.groups, {step["group"]: step["added"]}
        ) if step["added"] else []
        t0 = time.perf_counter()
        self.sess.solve_delta(added=added, removed=step["removed"])
        wall = time.perf_counter() - t0
        return wall, self.sess.last_mode or "?"

    @staticmethod
    def _steady(seen: dict) -> bool:
        """Nothing compiled and no cold tier served (``Run.since``)."""
        return seen["quiet"] and "native" not in seen["tiers"]

    def _establish(self) -> dict:
        """The session over the standing cluster, by one full solve; returns
        what the sidecar did for it (``Run.since``)."""
        mark = self.run.mark()
        t0 = time.perf_counter()
        self.sess.solve(self.first, self.inputs.provisioners,
                        self.inputs.catalog)
        seen = self.run.since(mark)
        log(f"session established: {(time.perf_counter() - t0) * 1000:.0f} "
            f"ms, {len(self.first)} pods, {seen}")
        return seen

    def warm_up(self) -> None:
        """Establish the session, take untimed steps of each kind until a
        whole pass of them shows no cold tier and no compile, then establish
        it AGAIN: the window starts from the standing cluster as the warm
        device tier packs it, however many warm passes this run needed and
        whichever tier served the first establishment."""
        self._establish()
        done = 0
        while done < self.warm_max:
            mark = self.run.mark()
            steps = []
            for spec in self.traffic["warm_steps"]:
                wall, mode = self._step(self.warm.next(
                    (spec["kind"], int(spec.get("n", 0)))))
                steps.append(f"{spec['kind']}:{mode}:{wall * 1000:.0f}")
                done += 1
            seen = self.run.since(mark)
            log(f"warm pass ({done} steps): {' '.join(steps)}; {seen}")
            if self._steady(seen):
                break
            if seen["compiling"]:
                time.sleep(0.5)
        else:
            raise RunFailed(f"delta steps still compiled or went to a cold "
                            f"tier after {self.warm_max} warm steps")
        # (more than once where the warm steps never reached the sidecar,
        # so that this is its second request and records a compile behind
        # it: the rehearsal's ``state_unchanged`` fault; no chip run has)
        for _ in range(3):
            seen = self._establish()
            if self._steady(seen):
                self.first = None
                info("warm_up", steps=done,
                     full_resends=self.sess.full_resends)
                return
            if seen["compiling"]:
                time.sleep(0.5)
        raise RunFailed("establishing the session still compiled or went to "
                        "a cold tier after the warm-up")

    def request(self) -> int:
        step = self.steps.next()
        wall, mode = self._step(step)
        m = self.modes.setdefault(mode, [0, 0.0])
        m[0] += 1
        m[1] += wall
        self.step_no += 1
        self.last = (step["kind"], mode)
        return len(step["added"])

    @property
    def unplaced_on_the_way(self) -> int:
        return sum(self.on_the_way)

    def whole(self) -> bool:
        """``drive`` asks once after each request, outside its wall: the pods
        that the step's answer holds infeasible are counted here, and at the
        end of a deck the boundary is kept (a copy: the session's containers
        are shared).  ``between_s`` is all the time spent here."""
        from plainref import Answer

        t0 = time.perf_counter()
        view = self.sess.result()
        out = set(view.infeasible)
        for pod in sorted(out - self.out)[:24 - len(self.left_out_at)]:
            self.left_out_at.append({
                "pass": len(self.boundaries) + 1, "step": self.step_no,
                "kind": self.last[0], "mode": self.last[1], "pod": pod,
                "why": str(view.infeasible[pod])[:120]})
        self.out = out
        boundary = not self.steps.deck
        if boundary:
            self.boundaries.append((list(self.steps.live),
                                    Answer.of_result(view),
                                    self.steps.since_down))
            self.on_the_way.append(0)
            self.step_no = 0
        else:
            self.on_the_way[-1] += len(out)
        self.between_s += time.perf_counter() - t0
        return boundary

    def close(self) -> None:
        self.full_resends = self.sess.full_resends
        self.sess.close()

    def cases(self) -> list:
        import gen

        costs = gen.boundary_costs(len(self.boundaries), self.cost_passes)
        return [(self.steps.settle(live).groups, ans, what)
                for (live, ans, _), what in zip(self.boundaries, costs)]

    def report(self, walls: list) -> None:
        import gen

        total = sum(n for n, _ in self.modes.values()) or 1
        info("delta_modes", full_resends=self.full_resends,
             decks=self.steps.decks_dealt, kinds=self.steps.kinds,
             modes={m: {"steps": n, "share": n / total,
                        "mean_ms": w / n * 1000.0}
                    for m, (n, w) in sorted(self.modes.items())})
        # every boundary in the order of the ``comparison`` line's
        # ``per_case`` (its view's cost, drifting from pass to pass, is what
        # incremental steps cost in $), the steps that left a pod without a
        # node, and what the look after every step added to the window
        costs = gen.boundary_costs(len(self.boundaries), self.cost_passes)
        info("boundaries", cost_passes=self.cost_passes,
             in_cost_ratio=costs.count("metric"),
             between_s=self.between_s,
             between_share=self.between_s / sum(walls),
             unplaced_on_the_way=self.unplaced_on_the_way,
             left_out_at=self.left_out_at,
             passes=[{"pass": k + 1, "pods": len(live),
                      "steps_since_scale_down": since,
                      "cost_compared_for": what,
                      "unplaced_on_the_way": self.on_the_way[k]}
                     for k, ((live, _, since), what)
                     in enumerate(zip(self.boundaries, costs))])


KINDS = {"burst": Burst, "reconcile": Reconcile}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


class Run:
    """What the traffic kinds share: the sidecar, the client-side registry,
    the program's input objects."""

    def __init__(self, sidecar, registry, tamper) -> None:
        self.sidecar, self.registry = sidecar, registry
        self.tamper = tamper or (lambda client: client)

    def mark(self) -> tuple:
        """The sidecar's counters now, for :meth:`since`."""
        import scrape as S

        return (S.scrape(self.sidecar.metrics_url),
                self.sidecar.command("device")["jit_programs_built"])

    def since(self, mark: tuple) -> dict:
        """What the sidecar did since ``mark``: the tiers that served, cold
        answers, behind-compiles recorded and running, programs jax built —
        and ``quiet``: no cold answer and nothing compiled."""
        import scrape as S

        before, built = mark
        after, built_now = self.mark()
        out = {"tiers": S.serving_tiers(before, after),
               "cold": S.delta(before, after, S.M_COLD_FALLBACKS),
               "compiles": S.delta(before, after, S.M_COMPILES),
               "compiling": S.metric(after, S.M_COMPILING),
               "jit_programs_built": built_now - built}
        out["quiet"] = not (out["cold"] or out["compiles"]
                            or out["compiling"] or out["jit_programs_built"])
        return out


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: int, *, platform: str = "tpu", scale: float = 1.0,
             tamper=None, sidecar_env: dict = None) -> dict:
    """Run one cell and return the last line's object (already checked
    against the contract).  ``platform``, ``scale`` and ``tamper`` are for
    the CPU rehearsal and the fault tests; the command passes none of them.
    """
    for path in (HERE, ROOT):
        if path not in sys.path:
            sys.path.insert(0, path)
    import gen
    import lastline
    import plainref
    import scrape as S
    import xplane

    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise RunFailed(f"workload {workload!r} is not in BENCHMARK.json")
    cfg = gen.load_config(cell["config"])
    traffic = gen.load_traffic(cell["traffic"])
    rows = gen.load_catalog(cfg["catalog"])
    run_dir = tempfile.mkdtemp(prefix="kb")
    sidecar = Sidecar(run_dir, platform, cell["chips"],
                      sidecar_env if sidecar_env is not None
                      else dict(os.environ))
    log(f"sidecar started (pid {sidecar.proc.pid})")
    try:
        # the traffic is made while the sidecar starts; none of it needs jax
        from karpenter_tpu.metrics import (
            FAULTS_RECOVERED,
            REMOTE_FALLBACK_SOLVES,
            Registry,
        )

        inputs = gen.ProgramInputs(cfg)
        differs = inputs.check_catalog(rows)
        if differs:
            raise RunFailed("the program's catalog is no longer the one in "
                            f"catalogs/{cfg['catalog']}.json: {differs}")
        registry = Registry()
        kind = KINDS[traffic["kind"]](inputs, cfg, traffic,
                                      seed & 0x7FFFFFFFFFFF, scale)
        log("traffic generated")
        hello = sidecar.wait_ready()
        log(f"sidecar ready after {time.perf_counter() - sidecar.started:.1f}"
            f"s: {hello}")
        if hello["platform"] != platform or hello["count"] < cell["chips"]:
            raise RunFailed(f"sidecar runs on {hello['platform']} x "
                            f"{hello['count']}")
        kind.connect(Run(sidecar, registry, tamper))
        kind.warm_up()

        # ---- the window: whole passes, the first that ends after
        # ``--seconds`` closes it; a traced run's is shorter and the profiler
        # is stopped once it has closed ----
        trace_dir = os.path.join(run_dir, "trace")
        limit = min(TRACE_MAX_S, seconds) if trace else seconds
        before = S.scrape(sidecar.metrics_url)
        built_before = sidecar.command("device")["jit_programs_built"]
        if trace:
            sidecar.command(f"trace_start {trace_dir}")
        setup_s = time.perf_counter() - T_START
        # a traffic mix whose cost_ratio reads fixed passes gets them all,
        # whatever the time: the metric is never read off fewer (a traced
        # window stays short and reports none)
        window = drive(kind, limit, passes=1 if trace else int(
            traffic.get("cost_passes", 1)))
        walls, failed, window_s = (window["walls"], window["failed"],
                                   window["window_s"])
        trace_window_s = (sidecar.command("trace_stop")["window_s"]
                          if trace else None)
        time.sleep(0.2)  # the last request's spans land when its trace ends
        after = S.scrape(sidecar.metrics_url)
        device = sidecar.command("device")
        built = device.pop("jit_programs_built") - built_before
        kind.close()
        local = (registry.counter(FAULTS_RECOVERED).get(
            {"site": "transport", "outcome": "fallback"})
            + registry.counter(REMOTE_FALLBACK_SOLVES).get())
        failed += int(local)  # answered by the client's own fallback
        rc = sidecar.stop()
        if rc != 0:
            raise RunFailed(f"sidecar exited {rc} on SIGTERM:\n"
                            f"{sidecar.log_tail()}")
        log(f"window closed: {len(walls)} requests in {window_s:.2f}s; "
            f"sidecar stopped (0)")

        # ---- the trace ----
        reduction = None
        if trace:
            reduction = xplane.reduce_trace(trace_dir, platform)
            device["busy_s"] = reduction["busy_s"]
            device["window_s"] = trace_window_s
            info("trace", requests=len(walls), window_s=trace_window_s,
                 busy_s=reduction["busy_s"], span_s=reduction["span_s"],
                 device_lines=reduction["device_lines"])

        # ---- the comparison (the sidecar is gone, its memory read) ----
        t_cmp = time.perf_counter()
        verdict = plainref.compare(
            kind.cases(), gen.provisioners_plain(cfg), rows["types"],
            rows["zones"], float(cfg["guarantees"]["cost_ceiling"]),
            unanswered=failed,
            unplaced_on_the_way=getattr(kind, "unplaced_on_the_way", 0))
        info("comparison", seconds=time.perf_counter() - t_cmp,
             compared=verdict["compared"], per_case=verdict["per_case"],
             first_violations=verdict["first_violations"])
        kind.report(walls)
        passes = whole_passes(window)
        info("window", requests=len(walls), **passes)
        info("tiers", window=S.serving_tiers(before, after),
             window_s=window_s, target=sidecar.target,
             compile_cache=hello.get("compile_cache"),
             cache_entries_at_start=hello.get("cache_entries"))

        # ---- the metrics ----
        n = len(walls)
        e2e = {"setup_s": setup_s, "solve_ms": passes["solve_ms"],
               "solve_p95_ms": p95(walls) * 1000.0,
               "pods_per_s": passes["pods_per_s"],
               "cost_ratio": verdict["cost_ratio"]}
        metrics = {}
        for decl in bench["end_to_end"]:
            if "workloads" in decl and workload not in decl["workloads"]:
                continue
            if e2e.get(decl["name"]) is not None:
                metrics[decl["name"]] = {"value": e2e[decl["name"]],
                                         "unit": decl["unit"]}
        metrics.update(read_layer_metrics(bench, workload, {
            "before": before, "after": after, "requests": n,
            "client_wall_s": sum(walls), "trace": reduction,
            "jit_programs_built": built,
            "trace_requests": n}))
        line = {"correct": verdict["correct"], "attempted": n,
                "failed": failed, "metrics": metrics, "device": device}
        if reduction:
            line["breakdown"] = reduction["breakdown"]
        line[lastline.COMPARED] = verdict["numbers"]
        for name, (value, limit) in verdict["numbers"].items():
            print(f"compared {name}: {value} (limit {limit})",
                  file=sys.stderr, flush=True)
        wrong = lastline.violations(json.dumps(line), workload, trace, bench)
        if wrong:
            raise RunFailed("the last line breaks the contract: "
                            + "; ".join(wrong))
        return line
    finally:
        if sidecar.proc.poll() is None:
            sidecar.proc.kill()
            sidecar.proc.wait()
        shutil.rmtree(run_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the sidecar gets the environment as it came; this process is the
    # operator and stays off the chip
    sidecar_env = dict(os.environ)
    os.environ["JAX_PLATFORMS"] = "cpu"
    if not os.path.isdir(os.path.join(ROOT, "karpenter_tpu")):
        print(f"run failed: no program beside the benchmark ({ROOT} holds no "
              "karpenter_tpu/)", file=sys.stderr, flush=True)
        return 2
    try:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        line = run_cell(bench, args.workload, args.seed, args.seconds,
                        args.trace, sidecar_env=sidecar_env)
    except RunFailed as err:
        print(f"run failed: {err}", file=sys.stderr, flush=True)
        return 1
    for earlier in EARLIER:
        print(earlier)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
