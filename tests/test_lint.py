"""ktlint (ISSUE 2): the AST solver-invariant analyzer.

Three surfaces:

1. **Rule fixtures** — every rule KT001-KT006 fires on a seeded violation
   and stays quiet on the compliant twin (a rule that can't fire guards
   nothing).
2. **Annotation grammar** — suppressions (with mandatory reason), fence
   annotations, guarded-by declarations.
3. **The gate** — the real package analyzes to ZERO unsuppressed findings,
   so tier-1 enforces the invariants with no CI changes; the CLI exits
   non-zero on findings.
"""

import textwrap

from karpenter_tpu.analysis import analyze_package, analyze_source
from karpenter_tpu.analysis.ktlint import analyze_files, load_source, main


def lint(src, path="karpenter_tpu/some.py"):
    return analyze_source(textwrap.dedent(src), path)


def rules_of(findings):
    return [f.rule for f in findings]


class TestKT001DeviceSync:
    HOT = "karpenter_tpu/solver/tpu.py"

    def test_fires_on_sync_outside_fence(self):
        src = """
        import numpy as np

        def hot_path(run, init):
            carry, ys = run(init)
            return float(np.asarray(carry[7]))
        """
        rules = rules_of(lint(src, self.HOT))
        # both the asarray-on-device and the float-on-device fire
        assert rules == ["KT001", "KT001"]

    def test_block_until_ready_always_fires(self):
        src = """
        def hot_path(x):
            x.block_until_ready()
        """
        assert rules_of(lint(src, self.HOT)) == ["KT001"]

    def test_item_on_device_value_fires(self):
        src = """
        def hot_path(carry):
            return carry.item()
        """
        assert rules_of(lint(src, self.HOT)) == ["KT001"]

    def test_host_numpy_is_clean(self):
        src = """
        import numpy as np

        def estimate(st):
            counts = np.asarray(st.counts)
            return float(counts.sum())
        """
        assert lint(src, self.HOT) == []

    def test_fence_annotation_allows(self):
        src = """
        import numpy as np

        # ktlint: fence the one-RTT D2H fence for this helper
        def fence_helper(run, init):
            carry, ys = run(init)
            return np.asarray(carry[7])
        """
        assert lint(src, self.HOT) == []

    def test_unannotated_method_is_not_a_fence(self):
        """The fence set lives in the source as annotations — there is no
        analyzer-side allowlist a rename could silently go stale against."""
        src = """
        import numpy as np

        class TpuSolver:
            def solve(self, run, init):
                carry, ys = run(init)
                return np.asarray(carry[7])
        """
        assert rules_of(lint(src, self.HOT)) == ["KT001"]

    def test_fence_comment_above_decorated_def(self):
        src = """
        import numpy as np

        class PendingTpuSolve:
            # ktlint: fence the async handle's one-RTT D2H fence
            def result(self, carry):
                return np.asarray(carry[7])
        """
        assert lint(src, self.HOT) == []

    def test_cold_files_are_not_scanned(self):
        src = """
        def anywhere(x):
            x.block_until_ready()
        """
        assert lint(src, "karpenter_tpu/solver/guard.py") == []

    def test_jnp_rooted_expression_taints(self):
        src = """
        import jax.numpy as jnp

        def hot_path(n):
            total = jnp.zeros(n).sum()
            return float(total)
        """
        assert rules_of(lint(src, self.HOT)) == ["KT001"]


class TestKT002RawClock:
    def test_time_time_fires(self):
        src = """
        import time

        def backoff():
            return time.time() + 300.0
        """
        assert rules_of(lint(src)) == ["KT002"]

    def test_monotonic_fires(self):
        src = """
        import time

        def deadline():
            return time.monotonic() + 5.0
        """
        assert rules_of(lint(src)) == ["KT002"]

    def test_clock_module_is_exempt(self):
        src = """
        import time as _time

        class Clock:
            def now(self):
                return _time.time()
        """
        assert lint(src, "karpenter_tpu/utils/clock.py") == []

    def test_perf_counter_is_exempt(self):
        src = """
        import time

        def measure():
            return time.perf_counter()
        """
        assert lint(src) == []

    def test_suppression_with_reason(self):
        src = """
        import time

        def deadline():
            return time.monotonic() + 5.0  # ktlint: allow[KT002] exit-path deadline
        """
        assert lint(src) == []

    def test_import_alias_is_tracked(self):
        src = """
        import time as t

        def backoff():
            return t.time() + 300.0
        """
        assert rules_of(lint(src)) == ["KT002"]

    def test_from_import_is_flagged_at_the_import(self):
        src = """
        from time import monotonic

        def deadline():
            return monotonic() + 5.0
        """
        findings = lint(src)
        assert rules_of(findings) == ["KT002"]
        assert findings[0].line == 2  # the import line, not the call

    def test_from_import_perf_counter_is_exempt(self):
        src = """
        from time import perf_counter

        def measure():
            return perf_counter()
        """
        assert lint(src) == []


class TestKT003MetricZeroInit:
    def test_labeled_counter_without_zero_init_fires(self):
        src = """
        def record(reg, backend):
            reg.counter(FOO_TOTAL).inc({"backend": backend})
        """
        assert rules_of(lint(src)) == ["KT003"]

    def test_zero_init_anywhere_in_package_satisfies(self):
        src = """
        def setup(reg):
            for b in ("native", "oracle"):
                reg.counter(FOO_TOTAL).inc({"backend": b}, value=0.0)

        def record(reg, backend):
            reg.counter(FOO_TOTAL).inc({"backend": backend})
        """
        assert lint(src) == []

    def test_cross_file_zero_init_is_seen(self):
        use = load_source(
            textwrap.dedent("""
            def record(reg, b):
                reg.counter(FOO_TOTAL).inc({"backend": b})
            """), "karpenter_tpu/a.py")
        init = load_source(
            textwrap.dedent("""
            def setup(reg):
                reg.counter(FOO_TOTAL).inc({"backend": "native"}, value=0.0)
            """), "karpenter_tpu/b.py")
        active, _ = analyze_files([use, init])
        assert active == []

    def test_unlabeled_counter_is_clean(self):
        src = """
        def record(reg):
            reg.counter(FOO_TOTAL).inc()
        """
        assert lint(src) == []

    def test_counter_bound_to_local_is_tracked(self):
        src = """
        def record(reg, backend):
            c = reg.counter(FOO_TOTAL)
            c.inc({"backend": backend})
        """
        assert rules_of(lint(src)) == ["KT003"]


class TestKT004LockDiscipline:
    def test_unguarded_mutation_fires(self):
        src = """
        import threading

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                self._jobs = []  # guarded-by: _lock

            def add(self, j):
                self._jobs.append(j)
        """
        findings = lint(src)
        assert rules_of(findings) == ["KT004"]
        assert "_jobs" in findings[0].message

    def test_guarded_access_is_clean(self):
        src = """
        import threading

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                self._jobs = []  # guarded-by: _lock

            def add(self, j):
                with self._lock:
                    self._jobs.append(j)
        """
        assert lint(src) == []

    def test_wrong_lock_fires(self):
        src = """
        import threading

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                self._other = threading.Lock()
                self._jobs = []  # guarded-by: _lock

            def add(self, j):
                with self._other:
                    self._jobs.append(j)
        """
        assert rules_of(lint(src)) == ["KT004"]

    def test_init_is_exempt_and_nested_funcs_are_checked(self):
        src = """
        import threading

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                self._jobs = []  # guarded-by: _lock
                self._jobs.append(0)  # construction is single-threaded

            def spawn(self):
                def work():
                    self._jobs.pop()
                return work
        """
        findings = lint(src)
        assert rules_of(findings) == ["KT004"]
        assert "work" in findings[0].message


class TestKT005BroadExcept:
    def test_silent_broad_except_fires(self):
        src = """
        def f():
            try:
                g()
            except Exception:
                pass
        """
        assert rules_of(lint(src)) == ["KT005"]

    def test_bare_except_and_base_exception_fire(self):
        src = """
        def f():
            try:
                g()
            except BaseException:
                x = 1
            try:
                g()
            except:
                x = 2
        """
        assert rules_of(lint(src)) == ["KT005", "KT005"]

    def test_reraise_and_log_are_clean(self):
        src = """
        def f(logger):
            try:
                g()
            except Exception:
                logger.warning("g failed", exc_info=True)
            try:
                g()
            except Exception:
                raise
        """
        assert lint(src) == []

    def test_narrow_except_is_clean(self):
        src = """
        def f():
            try:
                g()
            except (OSError, ValueError):
                pass
        """
        assert lint(src) == []

    def test_suppression_on_except_line(self):
        src = """
        def f(out):
            try:
                g()
            except Exception as err:  # ktlint: allow[KT005] fan-out contract
                out.append(err)
        """
        assert lint(src) == []


class TestKT006JitNondeterminism:
    def test_float64_in_jitted_fn_fires(self):
        src = """
        import jax
        import jax.numpy as jnp
        from functools import partial

        @partial(jax.jit, static_argnames=())
        def step(x):
            return x.astype(jnp.float64)
        """
        assert rules_of(lint(src)) == ["KT006"]

    def test_host_random_in_jitted_fn_fires(self):
        src = """
        import jax
        import random

        @jax.jit
        def step(x):
            return x * random.random()
        """
        assert rules_of(lint(src)) == ["KT006"]

    def test_jit_wrapped_name_is_in_scope(self):
        src = """
        import jax
        import numpy as np

        def kernel(x):
            return x.astype(np.float64)

        run = jax.jit(kernel)
        """
        assert rules_of(lint(src)) == ["KT006"]

    def test_host_code_is_out_of_scope(self):
        src = """
        import numpy as np
        import random

        def host_estimate(counts):
            return np.ceil(np.asarray(counts, dtype=np.float64)), random.random()
        """
        assert lint(src) == []

    def test_kernel_files_are_whole_file_scope(self):
        src = """
        import jax.numpy as jnp

        def water_fill(zc):
            return zc.astype("float64")
        """
        assert rules_of(lint(src, "karpenter_tpu/ops/masks.py")) == ["KT006"]

    def test_jax_random_is_exempt(self):
        src = """
        import jax

        @jax.jit
        def step(key, x):
            return x + jax.random.uniform(key)
        """
        assert lint(src) == []


class TestKT007SpanLifecycle:
    def test_bare_tracer_start_fires(self):
        src = """
        def solve(tracer):
            trace = tracer.start("solve")
            trace.annotate(backend="tpu")
        """
        assert rules_of(lint(src)) == ["KT007"]

    def test_with_form_is_clean(self):
        src = """
        def solve(tracer):
            with tracer.start("solve") as trace:
                with trace.span("tensorize") as sp:
                    sp.annotate(tier="identity")
                trace.record("window", 0.0, 1.0)
        """
        assert lint(src) == []

    def test_self_attribute_tracer_fires(self):
        src = """
        class Controller:
            def reconcile(self):
                trace = self._tracer.start("provision")
                return trace
        """
        assert rules_of(lint(src)) == ["KT007"]

    def test_bare_trace_span_fires(self):
        src = """
        def f(trace):
            sp = trace.span("launch")
            sp.annotate(n=1)
        """
        assert rules_of(lint(src)) == ["KT007"]

    def test_start_span_fires_regardless_of_receiver(self):
        src = """
        def f(t):
            return t.start_span("x")
        """
        assert rules_of(lint(src)) == ["KT007"]

    def test_thread_and_server_starts_never_match(self):
        src = """
        import threading

        def f(server):
            t = threading.Thread(target=f)
            t.start()
            server.start()
            self_thread = t
            self_thread.start()
        """
        assert lint(src) == []

    def test_suppression_with_reason(self):
        src = """
        def f(tracer):
            # ktlint: allow[KT007] handed to the dispatcher, closed in _finalize
            trace = tracer.start("solve")
            return trace
        """
        assert lint(src) == []


class TestKT008BucketGrid:
    HOT = "karpenter_tpu/solver/newkernel.py"

    def test_jit_inside_function_fires(self):
        src = """
        import jax

        def prepare(fn, x):
            return jax.jit(fn)(x)
        """
        assert rules_of(lint(src, self.HOT)) == ["KT008"]

    def test_partial_jit_inside_function_fires(self):
        src = """
        import jax
        from functools import partial

        def prepare(fn, x):
            run = partial(jax.jit, static_argnames=("NR",))(fn)
            return run(x)
        """
        assert rules_of(lint(src, self.HOT)) == ["KT008"]

    def test_jit_decorated_nested_def_fires(self):
        src = """
        import jax

        def prepare(x):
            @jax.jit
            def run(y):
                return y
            return run(x)
        """
        assert rules_of(lint(src, self.HOT)) == ["KT008"]

    def test_module_level_on_grid_jit_is_clean(self):
        src = """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("NR", "Z", "track"))
        def run_scan(consts, init, NR, Z, track):
            return consts
        """
        assert lint(src, self.HOT) == []

    def test_off_grid_static_argnames_fires(self):
        src = """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("NR", "batch_hint"))
        def run_scan(consts, NR, batch_hint):
            return consts
        """
        findings = lint(src, self.HOT)
        assert rules_of(findings) == ["KT008"]
        assert "batch_hint" in findings[0].message

    def test_off_path_files_are_out_of_scope(self):
        src = """
        import jax

        def controller_helper(fn, x):
            return jax.jit(fn)(x)
        """
        assert lint(src, "karpenter_tpu/controllers/provisioning.py") == []

    def test_suppression_with_reason(self):
        src = """
        import jax

        def replicate(mesh, value):
            # ktlint: allow[KT008] dryrun-only helper, two calls per process
            return jax.jit(lambda x: x)(value)
        """
        assert lint(src, self.HOT) == []

    def test_grid_vocabulary_matches_solve_dims(self, small_catalog):
        """The rule's static registry must cover exactly what solve_dims
        emits (plus the kernel statics) — a dims key added to the solver
        without registering it here would flag the solver's own kernels."""
        from karpenter_tpu.analysis.rules.kt008 import BUCKET_GRID_STATICS
        from karpenter_tpu.models.pod import PodSpec
        from karpenter_tpu.models.provisioner import Provisioner
        from karpenter_tpu.models.tensorize import tensorize
        from karpenter_tpu.solver.tpu import solve_dims

        st = tensorize([PodSpec(name="p0", requests={"cpu": 1.0})],
                       [Provisioner(name="default").with_defaults()],
                       small_catalog)
        dims = solve_dims(st, NE=0, node_budget=8)
        assert set(dims) <= BUCKET_GRID_STATICS
        assert {"zone_key", "ct_key"} <= BUCKET_GRID_STATICS


class TestKT009UncountedShed:
    RPC = "karpenter_tpu/service/handler.py"

    def test_fires_on_raise_without_inc(self):
        src = """
        from karpenter_tpu.admission import SolveShedError

        def admit(pclass):
            raise SolveShedError("queue full", pclass=pclass,
                                 reason="queue_full")
        """
        findings = lint(src, self.RPC)
        assert rules_of(findings) == ["KT009"]
        assert "karpenter_admission_shed_total" in findings[0].message

    def test_fires_on_construction_for_a_future(self):
        # the dispatcher resolving a future with the error (no raise) is
        # still an RPC-path rejection
        src = """
        from karpenter_tpu.admission import SolveDeadlineError

        def expire(fut, ticket):
            fut.set_exception(SolveDeadlineError("expired"))
        """
        assert rules_of(lint(src, self.RPC)) == ["KT009"]

    def test_quiet_with_counter_inc_in_same_function(self):
        src = """
        from karpenter_tpu.admission import SolveShedError
        from karpenter_tpu.metrics import ADMISSION_SHED

        def zero_init(registry):
            registry.counter(ADMISSION_SHED).inc(
                {"class": "batch", "reason": "queue_full"}, value=0.0)

        def admit(registry, pclass):
            registry.counter(ADMISSION_SHED).inc(
                {"class": pclass, "reason": "queue_full"})
            raise SolveShedError("queue full")
        """
        assert lint(src, self.RPC) == []

    def test_quiet_with_accounting_helper(self):
        src = """
        from karpenter_tpu.admission import SolveShedError

        def admit(self, pclass):
            self._count_shed(pclass, "queue_full", "full")
            raise SolveShedError("queue full")
        """
        assert lint(src, self.RPC) == []

    def test_out_of_scope_files_are_quiet(self):
        src = """
        from karpenter_tpu.admission import SolveShedError

        def poke():
            raise SolveShedError("not an RPC path")
        """
        assert lint(src, "karpenter_tpu/controllers/provisioning.py") == []

    def test_suppression_with_reason(self):
        src = """
        from karpenter_tpu.admission import SolveShedError

        def remap(err):
            # ktlint: allow[KT009] client-side re-map; serving side counted
            raise SolveShedError(str(err))
        """
        assert lint(src, self.RPC) == []


class TestKT015DeltaSessionDiscipline:
    SVC = "karpenter_tpu/service/delta.py"

    def test_fires_on_unlocked_table_access(self):
        src = """
        class Table:
            def peek(self, sid):
                return self._sessions.get(sid)
        """
        findings = lint(src, self.SVC)
        assert rules_of(findings) == ["KT015"]
        assert "_sessions" in findings[0].message

    def test_quiet_under_the_lock(self):
        src = """
        class Table:
            def get(self, sid):
                with self._lock:
                    return self._sessions.get(sid)
        """
        assert lint(src, self.SVC) == []

    def test_init_is_exempt(self):
        src = """
        class Table:
            def __init__(self):
                self._sessions = {}  # guarded-by: _lock
        """
        assert lint(src, self.SVC) == []

    def test_locked_suffix_helpers_are_exempt(self):
        # the repo's caller-holds-the-lock convention: the suffix is the
        # contract; callers must hold the with themselves
        src = """
        class Table:
            def _evict_expired_locked(self, now):
                self._sessions.clear()

            def clear(self):
                with self._lock:
                    self._evict_expired_locked(0.0)
        """
        assert lint(src, self.SVC) == []

    def test_fires_on_uncounted_delta_path_solve(self):
        src = """
        class Pipe:
            def _serve_delta(self, kwargs, info):
                return self.scheduler.solve(kwargs.pop("pods"), [], [])
        """
        findings = lint(src, "karpenter_tpu/service/server.py")
        assert rules_of(findings) == ["KT015"]
        assert "karpenter_solver_delta_rpc_total" in findings[0].message

    def test_uncounted_tensorize_on_delta_path_fires(self):
        src = """
        from karpenter_tpu.models.tensorize import tensorize

        def delta_reseed(pods, provs, its):
            return tensorize(pods, provs, its)
        """
        assert rules_of(lint(src, self.SVC)) == ["KT015"]

    def test_quiet_with_outcome_counter_in_same_function(self):
        src = """
        from karpenter_tpu.metrics import DELTA_RPC

        def zero_init(registry):
            registry.counter(DELTA_RPC).inc({"outcome": "delta"}, value=0.0)

        class Pipe:
            def _serve_delta(self, kwargs, info):
                result = self.scheduler.solve(kwargs.pop("pods"), [], [])
                self.registry.counter(DELTA_RPC).inc({"outcome": "delta"})
                return result
        """
        assert lint(src, "karpenter_tpu/service/server.py") == []

    def test_quiet_with_counting_funnel(self):
        src = """
        def zero_init(registry):
            registry.counter(DELTA_RPC).inc({"outcome": "delta"}, value=0.0)

        class Pipe:
            def _serve_delta(self, kwargs, info):
                def _counted(reply, outcome):
                    self.registry.counter(DELTA_RPC).inc({"outcome": outcome})
                    return reply, outcome
                result = self.scheduler.solve_delta(kwargs.pop("prev"))
                return _counted(result, "delta")
        """
        assert lint(src, "karpenter_tpu/service/server.py") == []

    def test_non_delta_functions_are_quiet(self):
        src = """
        class Pipe:
            def _dispatch_single(self, kwargs):
                return self.scheduler.solve(kwargs.pop("pods"), [], [])
        """
        assert lint(src, "karpenter_tpu/service/server.py") == []

    def test_out_of_scope_files_are_quiet(self):
        src = """
        class Sched:
            def solve_delta(self, prev):
                return self.solve(prev)
        """
        assert lint(src, "karpenter_tpu/solver/scheduler.py") == []

    def test_suppression_with_reason(self):
        src = """
        class Table:
            def stats(self):
                # ktlint: allow[KT015] single-field len read; torn reads benign
                return len(self._sessions)
        """
        assert lint(src, self.SVC) == []


class TestKT016FaultPlaneDiscipline:
    """ISSUE 12: serving-path code consults faults only via the FaultPlane
    facade (no raw random / KT_FAULT env probes in solver//service/), and
    every except that recovers from a faultable operation lands a recovery
    outcome in karpenter_faults_recovered_total in the same function."""

    SVC = "karpenter_tpu/service/server.py"
    SOLVER = "karpenter_tpu/solver/tpu.py"

    def test_fires_on_random_import_in_serving_code(self):
        src = """
        import random

        def backoff():
            return random.random()
        """
        findings = lint(src, self.SVC)
        assert "KT016" in rules_of(findings)

    def test_fires_on_from_random_import(self):
        src = """
        from random import uniform

        def backoff():
            return uniform(0, 1)
        """
        assert "KT016" in rules_of(lint(src, self.SOLVER))

    def test_fires_on_raw_fault_env_probe(self):
        src = """
        import os

        def chaotic():
            return os.environ.get("KT_FAULTS", "")
        """
        findings = lint(src, self.SVC)
        assert "KT016" in rules_of(findings)
        assert any("KT_FAULTS" in f.message for f in findings)

    def test_faults_package_is_the_sanctioned_home(self):
        src = """
        import os
        import random

        def plane():
            return os.environ.get("KT_FAULTS", "") and random.random()
        """
        assert "KT016" not in rules_of(lint(src, "karpenter_tpu/faults/plane.py"))

    def test_non_serving_dirs_are_quiet(self):
        # controllers/ etc. are out of scope — the plane threads through
        # solver/ and service/ only
        src = """
        import random

        def shuffle_candidates(c):
            random.shuffle(c)
        """
        assert "KT016" not in rules_of(lint(src, "karpenter_tpu/controllers/deprovisioning.py"))

    def test_other_env_probes_are_quiet(self):
        src = """
        import os

        def knob():
            return os.environ.get("KT_MAX_SLOTS", "8")
        """
        assert "KT016" not in rules_of(lint(src, self.SVC))

    def test_fires_on_uncounted_recovery(self):
        src = """
        class Pipe:
            def _serve_delta(self, entry, info):
                try:
                    return self._apply_delta_step(entry, info)
                except Exception:
                    self._delta_tab.drop(info["sid"], "error")
                    return None
        """
        findings = lint(src, self.SVC)
        assert "KT016" in rules_of(findings)
        assert any("karpenter_faults_recovered_total" in f.message
                   for f in findings)

    def test_quiet_with_count_recovery_helper(self):
        src = """
        from karpenter_tpu import faults

        class Pipe:
            def _serve_delta(self, entry, info):
                try:
                    return self._apply_delta_step(entry, info)
                except Exception:
                    faults.count_recovery(self.registry, "delta_step",
                                          "evicted")
                    return None
        """
        assert "KT016" not in rules_of(lint(src, self.SVC))

    def test_quiet_with_direct_counter_inc(self):
        src = """
        from karpenter_tpu.metrics import FAULTS_RECOVERED

        def zero_init(registry):
            registry.counter(FAULTS_RECOVERED).inc(
                {"site": "transport", "outcome": "retried"}, value=0.0)

        class Client:
            def solve_raw(self, req):
                try:
                    return self._solve(req)
                except Exception:
                    self.registry.counter(FAULTS_RECOVERED).inc(
                        {"site": "transport", "outcome": "retried"})
                    return self._solve(req)
        """
        assert "KT016" not in rules_of(lint(src, self.SVC))

    def test_bare_reraise_tail_is_exempt(self):
        # cleanup + re-raise surfaces the error typed: the RECOVERY (if
        # any) happens in the caller, which the rule judges separately
        src = """
        class Pipe:
            def _serve_delta(self, entry, info):
                try:
                    return self._apply_delta_step(entry, info)
                except Exception:
                    self._delta_tab.drop(info["sid"], "error")
                    raise
        """
        assert "KT016" not in rules_of(lint(src, self.SVC))

    def test_unfaultable_try_bodies_are_quiet(self):
        src = """
        class Pipe:
            def _bucket_of(self, kwargs):
                try:
                    return self.scheduler.bucket_key(kwargs)
                except Exception:
                    return None
        """
        assert "KT016" not in rules_of(lint(src, self.SVC))

    def test_suppression_with_reason(self):
        src = """
        class Pipe:
            def _serve_delta(self, entry, info):
                try:
                    return self._apply_delta_step(entry, info)
                # ktlint: allow[KT016] counted by the _counted funnel upstream
                except Exception:
                    return None
        """
        assert "KT016" not in rules_of(lint(src, self.SVC))


class TestKT017SpoolFacadeDiscipline:
    """ISSUE 13: the session spool's record/lease primitives
    (service/snapshot.py) may only be driven by the DeltaSessionTable
    facade (service/delta.py) — a drive-by spool access from the server
    or client layer bypasses the exactly-one-owner lease protocol."""

    SVC = "karpenter_tpu/service/server.py"

    def test_fires_on_lease_primitive_in_server_layer(self):
        src = """
        from . import snapshot as snap

        class Pipe:
            def _serve(self, sid):
                snap.claim_lease(self._spool_dir, sid, "me", 0.0, 10.0)
        """
        findings = lint(src, self.SVC)
        assert "KT017" in rules_of(findings)
        assert any("lease API" in f.message for f in findings)

    def test_fires_on_record_read_in_client_layer(self):
        src = """
        from . import snapshot as snap

        def peek(dir_path, sid):
            return snap.read_record(dir_path, sid)
        """
        assert "KT017" in rules_of(
            lint(src, "karpenter_tpu/service/client.py"))

    def test_fires_on_bare_name_call(self):
        src = """
        from .snapshot import release_lease

        def cleanup(dir_path, sid):
            release_lease(dir_path, sid, "me")
        """
        assert "KT017" in rules_of(lint(src, self.SVC))

    def test_snapshot_py_is_the_api_home(self):
        src = """
        def claim_lease(dir_path, sid, owner, now, ttl_s):
            return lease_path(dir_path, sid)
        """
        assert "KT017" not in rules_of(
            lint(src, "karpenter_tpu/service/snapshot.py"))

    def test_delta_py_is_the_facade(self):
        src = """
        from . import snapshot as snap

        class DeltaSessionTable:
            def adopt(self, dir_path, sid):
                blob = snap.read_record(dir_path, sid)
                return blob
        """
        assert "KT017" not in rules_of(
            lint(src, "karpenter_tpu/service/delta.py"))

    def test_out_of_scope_dirs_are_quiet(self):
        # the chaos harness and tests peek deliberately; solver/ has no
        # spool business and is out of scope
        src = """
        from karpenter_tpu.service import snapshot as snap

        def peek(d, sid):
            return snap.read_record(d, sid)
        """
        assert "KT017" not in rules_of(
            lint(src, "karpenter_tpu/solver/tpu.py"))

    def test_table_facade_calls_are_quiet(self):
        # driving the spool THROUGH the table is the sanctioned shape
        src = """
        class Pipe:
            def _serve(self, sid):
                entry = self._delta_tab.adopt(self._spool_dir, sid)
                self._delta_tab.handoff(sid, self._spool_dir)
                return entry
        """
        assert "KT017" not in rules_of(lint(src, self.SVC))

    def test_suppression_with_reason(self):
        src = """
        from . import snapshot as snap

        class Pipe:
            def _debug(self, sid):
                # ktlint: allow[KT017] read-only statusz forensics dump
                return snap.lease_state(self._spool_dir, sid)
        """
        assert "KT017" not in rules_of(lint(src, self.SVC))


class TestKT018AddressableShardFence:
    """ISSUE 14: megabatch extraction must fence through the
    addressable-shard accessor (solver/tpu.read_slot_rows) — a raw
    np.asarray / device_get on the slot-stacked carry (carry_b/ys_b) is
    the whole-batch-readback bug class the per-host fence removed: every
    host pays DCN for slots it does not own."""

    TPU = "karpenter_tpu/solver/tpu.py"

    def test_fires_on_whole_batch_asarray_in_results(self):
        src = """
        import numpy as np

        class PendingMegaSolve:
            def results(self):
                np.asarray(self.carry_b[7])
                return [np.asarray(x) for x in self.carry_b]
        """
        findings = lint(src, self.TPU)
        assert "KT018" in rules_of(findings)
        assert any("read_slot_rows" in (f.hint or "") for f in findings)

    def test_fires_on_device_get_of_stacked_ys(self):
        src = """
        import jax

        def demux(handle):
            return jax.device_get(handle.ys_b)
        """
        assert "KT018" in rules_of(
            lint(src, "karpenter_tpu/service/server.py"))

    def test_fires_on_bare_stacked_name(self):
        src = """
        import numpy as np

        def fence(carry_b):
            np.asarray(carry_b[7])
        """
        assert "KT018" in rules_of(lint(src, self.TPU))

    def test_accessor_function_is_the_sanctioned_home(self):
        src = """
        import numpy as np

        def read_slot_rows(arrays, local_only=False):
            carry_b = arrays[0]
            return np.asarray(carry_b)
        """
        assert "KT018" not in rules_of(lint(src, self.TPU))

    def test_accessor_routed_read_is_quiet(self):
        src = """
        class PendingMegaSolve:
            def results(self):
                rows, br, bt = read_slot_rows(
                    [self.carry_b[7]], local_only=True)
                return rows
        """
        assert "KT018" not in rules_of(lint(src, self.TPU))

    def test_single_solve_carry_is_out_of_scope(self):
        # the single-solve handle's carry is genuinely global: its one
        # result needs every shard, so the whole read is the contract
        src = """
        import numpy as np

        class PendingTpuSolve:
            def result(self):
                np.asarray(self.carry[7])
        """
        assert "KT018" not in rules_of(lint(src, self.TPU))

    def test_out_of_scope_files_are_quiet(self):
        # scripts/tests/dryruns read carries deliberately
        src = """
        import numpy as np

        def probe(handle):
            return np.asarray(handle.carry_b[7])
        """
        assert "KT018" not in rules_of(
            lint(src, "scripts/chaos_drive.py"))

    def test_suppression_with_reason(self):
        src = """
        import numpy as np

        def fence(carry_b):
            # ktlint: allow[KT018] single-process unit fixture readback
            np.asarray(carry_b[7])
        """
        assert "KT018" not in rules_of(lint(src, self.TPU))


class TestKT019WireTraceContext:
    """ISSUE 15: every wire-crossing send site must forward the trace
    context (trace_id= into codec.encode_request), and every server entry
    that decodes a remote parent must open its trace through the
    Tracer.start_remote facade — one non-compliant hop orphans every
    downstream hop's tree in /fleetz."""

    CLIENT = "karpenter_tpu/service/client.py"
    FORWARD = "karpenter_tpu/parallel/forward.py"
    SERVER = "karpenter_tpu/service/server.py"

    def test_fires_on_contextless_client_encode(self):
        src = """
        def solve(self, pods):
            req = codec.encode_request(pods, provs, types,
                                       backend=self.backend)
            return self.client.solve_raw(req)
        """
        findings = lint(src, self.CLIENT)
        assert "KT019" in rules_of(findings)
        assert any("trace_id" in (f.hint or "") for f in findings)

    def test_fires_on_contextless_forward_shim_encode(self):
        src = """
        def forward(self, kwargs, err):
            req = codec.encode_request(kwargs["pods"], kwargs["provs"],
                                       kwargs["types"])
            return self._client(endpoint).solve_raw(req)
        """
        assert "KT019" in rules_of(lint(src, self.FORWARD))

    def test_context_forwarding_send_is_quiet(self):
        src = """
        def solve(self, pods, trace):
            tid, parent = trace.wire_context()
            req = codec.encode_request(pods, provs, types,
                                       trace_id=tid, parent_span=parent)
            return self.client.solve_raw(req)
        """
        assert "KT019" not in rules_of(lint(src, self.CLIENT))

    def test_fires_on_decode_without_the_facade(self):
        src = """
        class SolverService:
            def Solve(self, request, context):
                tid, parent = codec.decode_trace_fields(request)
                with self.tracer.start("solve", rpc="Solve") as trace:
                    return self._serve(request, trace)
        """
        findings = lint(src, self.SERVER)
        assert "KT019" in rules_of(findings)
        assert any("start_remote" in f.message for f in findings)

    def test_facade_adopting_entry_is_quiet(self):
        src = """
        class SolverService:
            def Solve(self, request, context):
                tid, parent = codec.decode_trace_fields(request)
                with self.tracer.start_remote("solve", tid, parent,
                                              rpc="Solve") as trace:
                    return self._serve(request, trace)
        """
        assert "KT019" not in rules_of(lint(src, self.SERVER))

    def test_warm_request_encode_is_out_of_scope(self):
        # warmup is fire-and-forget — never part of a request tree
        src = """
        def warm(self, provs, types):
            return codec.encode_warm_request(provs, types)
        """
        assert "KT019" not in rules_of(lint(src, self.CLIENT))

    def test_out_of_scope_files_are_quiet(self):
        # bench/scripts drive the facades, which already comply
        src = """
        def drive(pods):
            return codec.encode_request(pods, provs, types)
        """
        assert "KT019" not in rules_of(lint(src, "bench.py"))
        assert "KT019" not in rules_of(
            lint(src, "scripts/chaos_drive.py"))

    def test_suppression_with_reason(self):
        src = """
        def resend(self, req):
            # ktlint: allow[KT019] context already on the re-sent request
            return codec.encode_request(req.pods, req.provs, req.types)
        """
        assert "KT019" not in rules_of(lint(src, self.CLIENT))


class TestSuppressionGrammar:
    SRC = """
    import time

    def f():
        return time.time()
    """

    def test_bare_allow_reports_kt000_and_does_not_suppress(self):
        src = """
        import time

        def f():
            return time.time()  # ktlint: allow[KT002]
        """
        rules = rules_of(lint(src))
        assert "KT000" in rules and "KT002" in rules

    def test_comment_block_above_suppresses(self):
        src = """
        import time

        def f():
            # ktlint: allow[KT002] documented exit-path stopwatch
            # (second comment line between allow and the finding is fine)
            return time.time()
        """
        assert lint(src) == []

    def test_wrong_rule_id_does_not_suppress(self):
        src = """
        import time

        def f():
            return time.time()  # ktlint: allow[KT005] wrong rule
        """
        assert rules_of(lint(src)) == ["KT002"]

    def test_suppressed_findings_are_reported_separately(self):
        src = textwrap.dedent("""
        import time

        def f():
            return time.time()  # ktlint: allow[KT002] reasoned
        """)
        active, suppressed = analyze_files(
            [load_source(src, "karpenter_tpu/x.py")])
        assert active == []
        assert rules_of(suppressed) == ["KT002"]


class TestPackageGate:
    def test_package_has_zero_unsuppressed_findings(self):
        active, suppressed, n_files = analyze_package()
        assert n_files > 60  # the whole package was actually scanned
        assert active == [], "\n".join(f.format() for f in active)
        # every suppression in the tree carries a reason by construction
        # (reason-less ones surface as KT000 above); the count is a canary
        # against silent suppression creep (bumped PR 15: the fleet-
        # tracing KT005s — adoption-provenance lease read, /statusz extra
        # provider, per-peer /fleetz fetch, replay outcome boxing +
        # teardown — all best-effort observability paths)
        assert len(suppressed) < 52

    def test_main_exit_codes(self, tmp_path):
        bad = tmp_path / "karpenter_tpu" / "bad.py"
        bad.parent.mkdir()
        bad.write_text("import time\n\ndef f():\n    return time.time()\n")
        assert main([str(bad)]) == 1
        good = tmp_path / "karpenter_tpu" / "good.py"
        good.write_text("def f():\n    return 1\n")
        assert main([str(good)]) == 0
        assert main([]) == 0  # the package itself is the default target

    def test_select_filters_rules(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("import time\n\ndef f():\n    return time.time()\n")
        assert main([str(bad), "--select", "KT005"]) == 0
        assert main([str(bad), "--select", "KT002"]) == 1


class TestKT010LoopOfDispatch:
    CTRL = "karpenter_tpu/controllers/deprovisioning.py"

    def test_fires_on_simulate_in_for_loop(self):
        src = """
        def pass_(self, cands):
            for ns in cands:
                attempt = self._simulate([ns])
                if attempt is not None:
                    return attempt
        """
        findings = lint(src, self.CTRL)
        assert rules_of(findings) == ["KT010"]
        assert "per iteration" in findings[0].message

    def test_fires_on_scheduler_solve_in_while_loop(self):
        src = """
        def pass_(self, queue):
            while queue:
                req = queue.pop()
                self.scheduler.solve(req.pods, req.provs, req.types)
        """
        assert rules_of(lint(src, self.CTRL)) == ["KT010"]

    def test_fires_on_solve_what_if_in_loop(self):
        src = """
        def pass_(self, cands):
            results = []
            for names in cands:
                results.append(self._solve_what_if([], names))
            return results
        """
        assert rules_of(lint(src, self.CTRL)) == ["KT010"]

    def test_fires_on_simulate_in_comprehension(self):
        # a comprehension is the for-loop-of-dispatch spelled on one line
        src = """
        def pass_(self, cands):
            return [self._simulate([ns]) for ns in cands]
        """
        assert rules_of(lint(src, self.CTRL)) == ["KT010"]

    def test_fires_on_solve_in_generator_expression(self):
        src = """
        def pass_(self, cands):
            return any(self.scheduler.solve(c.pods, c.provs, c.types)
                       for c in cands)
        """
        assert rules_of(lint(src, self.CTRL)) == ["KT010"]

    def test_allow_on_comprehension_line(self):
        src = """
        def pass_(self, cands):
            return [self._simulate([ns]) for ns in cands]  # ktlint: allow[KT010] cands has one entry by contract
        """
        assert lint(src, self.CTRL) == []

    def test_quiet_outside_a_loop(self):
        src = """
        def one(self, ns):
            return self._simulate([ns])
        """
        assert lint(src, self.CTRL) == []

    def test_quiet_outside_controllers(self):
        src = """
        def sweep(self, cands):
            for c in cands:
                self.scheduler.solve(c.pods, c.provs, c.types)
        """
        assert lint(src, "karpenter_tpu/solver/consolidation.py") == []

    def test_quiet_when_loop_body_is_a_deferred_callable(self):
        # a closure built per iteration is not a per-iteration dispatch —
        # the collector pattern batches them into one device call later
        src = """
        def collect(self, cands):
            thunks = []
            for c in cands:
                thunks.append(lambda c=c: self._simulate([c]))
            return thunks
        """
        assert lint(src, self.CTRL) == []

    def test_allow_on_call_line(self):
        src = """
        def search(self, cands, lo, hi):
            while lo <= hi:
                mid = (lo + hi) // 2
                a = self._simulate(cands[:mid])  # ktlint: allow[KT010] binary search is sequential
                lo, hi = (mid + 1, hi) if a else (lo, mid - 1)
        """
        assert lint(src, self.CTRL) == []

    def test_allow_on_loop_header_comment(self):
        src = """
        def search(self, cands, lo, hi):
            # ktlint: allow[KT010] each probe depends on the previous answer
            while lo <= hi:
                mid = (lo + hi) // 2
                a = self._simulate(cands[:mid])
                lo, hi = (mid + 1, hi) if a else (lo, mid - 1)
        """
        assert lint(src, self.CTRL) == []

    def test_reasonless_allow_is_malformed(self):
        src = """
        def pass_(self, cands):
            for ns in cands:
                self._simulate([ns])  # ktlint: allow[KT010]
        """
        assert "KT000" in rules_of(lint(src, self.CTRL))


class TestKT011ShardingConstruction:
    HOT = "karpenter_tpu/solver/newdispatch.py"

    def test_named_sharding_inside_function_fires(self):
        src = """
        from jax.sharding import NamedSharding, PartitionSpec as P

        def dispatch(mesh, arrays):
            sh = NamedSharding(mesh, P("slots"))
            return [a for a in arrays]
        """
        findings = lint(src, self.HOT)
        assert rules_of(findings) == ["KT011"]
        assert "NamedSharding" in findings[0].message

    def test_mesh_construction_inside_function_fires(self):
        src = """
        from jax.sharding import Mesh

        def flush(devices):
            return Mesh(devices, ("slots",))
        """
        assert rules_of(lint(src, self.HOT)) == ["KT011"]

    def test_raw_device_put_fires(self):
        src = """
        import jax

        def stack(vals, sh):
            return jax.device_put(vals, sh)
        """
        findings = lint(src, self.HOT)
        assert rules_of(findings) == ["KT011"]
        assert "device_put" in findings[0].message

    def test_nested_closure_walks_with_enclosing(self):
        src = """
        import jax

        def dispatch(mesh, vals, sh):
            def stack(v):
                return jax.device_put(v, sh)
            return [stack(v) for v in vals]
        """
        assert rules_of(lint(src, self.HOT)) == ["KT011"]

    def test_module_level_layout_is_clean(self):
        src = """
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        MESH = Mesh(jax.devices(), ("slots",))
        SHARDING = NamedSharding(MESH, P("slots"))
        """
        assert lint(src, self.HOT) == []

    def test_parallel_factories_are_clean(self):
        src = """
        from karpenter_tpu.parallel.distributed import put_sharded
        from karpenter_tpu.parallel.mesh import slot_sharding

        def dispatch(mesh, vals):
            sh = slot_sharding(mesh)
            return [put_sharded(v, sh) for v in vals]
        """
        assert lint(src, self.HOT) == []

    def test_parallel_package_out_of_scope(self):
        # the sanctioned construction home: the cached factories themselves
        src = """
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        def slot_mesh(mesh):
            return Mesh(mesh.devices.reshape(-1), ("slots",))
        """
        assert lint(src, "karpenter_tpu/parallel/mesh.py") == []

    def test_batcher_in_scope(self):
        src = """
        import jax

        def coalesce(vals, sh):
            return jax.device_put(vals, sh)
        """
        assert rules_of(lint(src, "karpenter_tpu/batcher.py")) == ["KT011"]

    def test_suppression_with_reason(self):
        src = """
        import jax

        def measure(args, res_i):
            # ktlint: allow[KT011] benchmark-only perturbed re-placement
            return (jax.device_put(res_i),) + args[1:]
        """
        assert lint(src, self.HOT) == []


# ---------------------------------------------------------------------------
# whole-program engine (ISSUE 9): call graph + KT012/KT013/KT014
# ---------------------------------------------------------------------------


def sources(*pairs):
    return [load_source(textwrap.dedent(src), path) for path, src in pairs]


def lint_files(pairs, rules):
    active, _ = analyze_files(sources(*pairs), rules=rules)
    return active


class TestCallGraphCore:
    """The project symbol table + call graph the whole-program rules share:
    resolution through facades, graceful degradation on unresolved calls,
    recursion termination, and the content-hash summary cache."""

    def test_facade_boundary_edge_resolves(self):
        from karpenter_tpu.analysis.callgraph import build_project

        files = sources(
            ("karpenter_tpu/pipe.py", """
             from .sched import BatchScheduler

             class SolvePipeline:
                 def __init__(self, scheduler: BatchScheduler):
                     self.scheduler = scheduler

                 def drive(self):
                     return self.scheduler.solve()
             """),
            ("karpenter_tpu/sched.py", """
             class BatchScheduler:
                 def solve(self):
                     return 1
             """),
        )
        project = build_project(files)
        node = project.funcs["karpenter_tpu.pipe:SolvePipeline.drive"]
        assert [c for _l, c, _n in node.edges] == [
            "karpenter_tpu.sched:BatchScheduler.solve"]

    def test_constructor_attr_and_local_var_types_resolve(self):
        from karpenter_tpu.analysis.callgraph import build_project

        files = sources(("karpenter_tpu/m.py", """
            class Inner:
                def grab(self):
                    return 1

            class Outer:
                def __init__(self, inner=None):
                    self.inner = inner or Inner()

                def via_attr(self):
                    return self.inner.grab()

            def via_local():
                x = Inner()
                return x.grab()
            """))
        project = build_project(files)
        grab = "karpenter_tpu.m:Inner.grab"
        assert [c for _l, c, _n in
                project.funcs["karpenter_tpu.m:Outer.via_attr"].edges] == [grab]
        assert grab in [c for _l, c, _n in
                        project.funcs["karpenter_tpu.m:via_local"].edges]

    def test_unresolved_calls_degrade_gracefully(self):
        from karpenter_tpu.analysis.callgraph import build_project

        files = sources(("karpenter_tpu/m.py", """
            def f(anything):
                anything.method()
                getattr(anything, "x")()
                unknown_name(1)
            """))
        project = build_project(files)   # must not raise
        assert project.funcs["karpenter_tpu.m:f"].edges == []
        assert any(name == "anything.method"
                   for _fid, _line, name in project.unresolved)

    def test_base_class_method_resolution(self):
        from karpenter_tpu.analysis.callgraph import build_project

        files = sources(("karpenter_tpu/m.py", """
            class Base:
                def shared(self):
                    return 1

            class Child(Base):
                def go(self):
                    return self.shared()
            """))
        project = build_project(files)
        assert [c for _l, c, _n in
                project.funcs["karpenter_tpu.m:Child.go"].edges] == [
            "karpenter_tpu.m:Base.shared"]

    def test_summary_cache_hit_path(self, tmp_path):
        from karpenter_tpu.analysis.callgraph import (
            Project, SummaryCache, build_project)

        files = sources(
            ("karpenter_tpu/a.py", "def f():\n    return g()\n\ndef g():\n    return 1\n"),
            ("karpenter_tpu/b.py", "def h():\n    return 2\n"),
        )
        cache_file = tmp_path / "cache.json"
        c1 = SummaryCache(path=cache_file)
        p1 = Project.build(files, cache=c1)
        assert (c1.hits, c1.misses) == (0, 2)
        assert cache_file.exists()
        # same content -> every file served from the persisted cache
        c2 = SummaryCache(path=cache_file)
        p2 = Project.build(files, cache=c2)
        assert (c2.hits, c2.misses) == (2, 0)
        assert sorted(p2.funcs) == sorted(p1.funcs)
        # content change -> that file re-extracts, the other still hits
        files2 = sources(
            ("karpenter_tpu/a.py", "def f():\n    return 3\n"),
            ("karpenter_tpu/b.py", "def h():\n    return 2\n"),
        )
        c3 = SummaryCache(path=cache_file)
        Project.build(files2, cache=c3)
        assert (c3.hits, c3.misses) == (1, 1)

    def test_corrupt_cache_is_discarded(self, tmp_path):
        from karpenter_tpu.analysis.callgraph import Project, SummaryCache

        cache_file = tmp_path / "cache.json"
        cache_file.write_text("{not json")
        files = sources(("karpenter_tpu/a.py", "def f():\n    return 1\n"))
        cache = SummaryCache(path=cache_file)
        project = Project.build(files, cache=cache)  # must not raise
        assert "karpenter_tpu.a:f" in project.funcs


class TestKT012LockOrder:
    from karpenter_tpu.analysis.rules import kt012 as RULE

    CYCLE = ("karpenter_tpu/m.py", """
        import threading

        class A:
            def __init__(self, b=None):
                self._lock = threading.Lock()
                self.b = b or B()

            def outer(self):
                with self._lock:
                    self.b.grab()

            def inner(self):
                with self._lock:
                    pass

        class B:
            def __init__(self, a: "A" = None):
                self._lock = threading.Lock()
                self.a = a

            def grab(self):
                with self._lock:
                    pass

            def outer(self):
                with self._lock:
                    self.a.inner()
        """)

    def test_interprocedural_cycle_fires_with_witnesses(self):
        findings = lint_files([self.CYCLE], [self.RULE])
        assert rules_of(findings) == ["KT012"]
        msg = findings[0].message
        assert "A._lock" in msg and "B._lock" in msg
        assert "witness" in msg and "A.outer" in msg and "B.outer" in msg

    def test_consistent_order_is_quiet(self):
        src = ("karpenter_tpu/m.py", """
        import threading

        class A:
            def __init__(self):
                self._lock = threading.Lock()
                self.b = B()

            def outer(self):
                with self._lock:
                    self.b.grab()

        class B:
            def __init__(self):
                self._lock = threading.Lock()

            def grab(self):
                with self._lock:
                    pass
        """)
        assert lint_files([src], [self.RULE]) == []

    def test_self_nesting_of_plain_lock_fires(self):
        src = ("karpenter_tpu/m.py", """
        import threading

        class A:
            def __init__(self):
                self._lock = threading.Lock()

            def outer(self):
                with self._lock:
                    self.helper()

            def helper(self):
                with self._lock:
                    pass
        """)
        findings = lint_files([src], [self.RULE])
        assert rules_of(findings) == ["KT012"]
        assert "non-reentrant" in findings[0].message

    def test_reentrant_self_nesting_is_quiet(self):
        src = ("karpenter_tpu/m.py", """
        import threading

        class A:
            def __init__(self):
                self._lock = threading.RLock()
                self._cond = threading.Condition()

            def outer(self):
                with self._lock:
                    self.helper()

            def helper(self):
                with self._lock:
                    pass

            def put(self):
                with self._cond:
                    self.bump()

            def bump(self):
                with self._cond:
                    pass
        """)
        assert lint_files([src], [self.RULE]) == []

    def test_recursion_terminates(self):
        src = ("karpenter_tpu/m.py", """
        import threading

        class A:
            def __init__(self):
                self._lock = threading.Lock()

            def f(self, n):
                with self._lock:
                    pass
                return self.g(n)

            def g(self, n):
                return self.f(n - 1) if n else 0
        """)
        assert lint_files([src], [self.RULE]) == []

    def test_closure_acquisitions_contribute_no_edge(self):
        # a callback body runs where it is CALLED, not where it is written:
        # static edges from closures would cry wolf (the runtime watcher
        # covers the real callback nestings)
        src = ("karpenter_tpu/m.py", """
        import threading

        class A:
            def __init__(self):
                self._lock = threading.Lock()
                self._other = threading.Lock()

            def outer(self):
                with self._lock:
                    return lambda: self.takes_other()

            def takes_other(self):
                with self._other:
                    self.back()

            def back(self):
                with self._lock:
                    pass
        """)
        # _other -> _lock exists (takes_other), but _lock -> _other only
        # via the lambda, which must NOT edge: no cycle, no finding
        assert lint_files([src], [self.RULE]) == []

    def test_suppression_with_reason(self):
        path, src = self.CYCLE
        src = src.replace(
            "            def outer(self):\n                with self._lock:\n                    self.b.grab()",
            "            def outer(self):\n                # ktlint: allow[KT012] B is always a fresh private instance here\n                with self._lock:\n                    self.b.grab()",
            1)
        assert lint_files([(path, src)], [self.RULE]) == []

    def test_lock_order_is_a_linear_extension(self):
        from karpenter_tpu.analysis.rules.kt012 import lock_graph, lock_order

        files = sources(self.CYCLE[:1] + (self.CYCLE[1].replace(
            "def outer(self):\n                with self._lock:\n                    self.a.inner()",
            "def outer(self):\n                pass", 1),))
        order = lock_order(files)
        _nodes, edges, _kinds = lock_graph(files)
        idx = {n: i for i, n in enumerate(order)}
        for (s, d) in edges:
            if s != d:
                assert idx[s] < idx[d]


class TestKT013FenceReachability:
    from karpenter_tpu.analysis.rules import kt013 as RULE

    def test_reachable_sync_fires_with_chain(self):
        files = [("karpenter_tpu/solver/scheduler.py", """
        import numpy as np

        class BatchScheduler:
            def solve(self, run, init):
                return finish(run, init)

        def finish(run, init):
            carry, ys = run(init)
            return np.asarray(carry[7])
        """)]
        findings = lint_files(files, [self.RULE])
        assert rules_of(findings) == ["KT013"]
        assert "BatchScheduler.solve -> finish" in findings[0].message

    def test_fence_on_the_path_absorbs(self):
        files = [("karpenter_tpu/solver/scheduler.py", """
        import numpy as np

        class BatchScheduler:
            def solve(self, run, init):
                return finish(run, init)

        # ktlint: fence the one-RTT D2H read IS this helper's job
        def finish(run, init):
            carry, ys = run(init)
            return np.asarray(carry[7])
        """)]
        assert lint_files(files, [self.RULE]) == []

    def test_host_numpy_stays_quiet_interprocedurally(self):
        files = [("karpenter_tpu/solver/scheduler.py", """
        import numpy as np

        class BatchScheduler:
            def solve(self, st):
                return estimate(st)

        def estimate(st):
            counts = np.asarray(st.counts)
            return float(counts.sum())
        """)]
        assert lint_files(files, [self.RULE]) == []

    def test_jitted_call_readback_fires_across_modules(self):
        """The PR 6/7 review-round bug class: a controller tick reaching an
        eager kernel-readback helper (np.asarray over a jitted call) in
        another module with no fence on the path — the shape
        screen_subset_deletes had before its fence annotation."""
        files = [
            ("karpenter_tpu/controllers/deprovisioning.py", """
             from ..solver.consolidation import screen

             class DeprovisioningController:
                 def reconcile(self):
                     return screen([1])
             """),
            ("karpenter_tpu/solver/consolidation.py", """
             import jax
             import numpy as np
             from functools import partial

             @partial(jax.jit)
             def _kernel(x):
                 return x

             def screen(args):
                 return np.asarray(_kernel(args))
             """),
        ]
        findings = lint_files(files, [self.RULE])
        assert rules_of(findings) == ["KT013"]
        assert "DeprovisioningController.reconcile -> screen" \
            in findings[0].message

    def test_fence_annotation_fixes_the_jitted_readback(self):
        files = [
            ("karpenter_tpu/controllers/deprovisioning.py", """
             from ..solver.consolidation import screen

             class DeprovisioningController:
                 def reconcile(self):
                     return screen([1])
             """),
            ("karpenter_tpu/solver/consolidation.py", """
             import jax
             import numpy as np
             from functools import partial

             @partial(jax.jit)
             def _kernel(x):
                 return x

             # ktlint: fence the screen IS the sync point by design
             def screen(args):
                 return np.asarray(_kernel(args))
             """),
        ]
        assert lint_files(files, [self.RULE]) == []

    def test_recursive_call_chain_terminates(self):
        files = [("karpenter_tpu/solver/scheduler.py", """
        class BatchScheduler:
            def solve(self, n):
                return helper(n)

        def helper(n):
            return helper(n - 1) if n else other(n)

        def other(n):
            return helper(n)
        """)]
        assert lint_files(files, [self.RULE]) == []

    def test_stale_entry_point_fires_when_class_remains(self):
        files = [("karpenter_tpu/solver/scheduler.py", """
        class BatchScheduler:
            def solve_renamed(self):
                return 1
        """)]
        findings = lint_files(files, [self.RULE])
        assert "KT013" in rules_of(findings)
        assert "ENTRY_POINTS" in findings[0].message

    def test_fixture_without_the_class_stays_quiet(self):
        files = [("karpenter_tpu/solver/scheduler.py", """
        def unrelated():
            return 1
        """)]
        assert lint_files(files, [self.RULE]) == []

    def test_suppression_on_the_sync_line(self):
        files = [("karpenter_tpu/solver/scheduler.py", """
        import numpy as np

        class BatchScheduler:
            def solve(self, run, init):
                carry, ys = run(init)
                return np.asarray(carry[7])  # ktlint: allow[KT013] cold path by contract
        """)]
        assert lint_files(files, [self.RULE]) == []

    def test_every_entry_point_resolves_in_the_real_package(self):
        """The anti-staleness gate the per-file finding cannot give: a
        class-level rename must fail HERE, not silently shrink the audited
        surface."""
        from karpenter_tpu.analysis.callgraph import build_project
        from karpenter_tpu.analysis.ktlint import collect_package_files
        from karpenter_tpu.analysis.rules.kt013 import ENTRY_POINTS

        project = build_project(collect_package_files())
        missing = [f"{s}:{q}" for s, q in ENTRY_POINTS
                   if project.find_function(s, q) is None]
        assert missing == []


class TestKT014CompileSurface:
    from karpenter_tpu.analysis.rules import kt014 as RULE

    TPU_OK = ("karpenter_tpu/solver/tpu.py", """
        MEGA_MAX_SLOTS = 32

        def solve_dims(st):
            return dict(G=1, C=1, NR=1, NE_pad=1, S=1, P=1, D=1, R=1,
                        Z=1, K=1, W=1, track=True, a=1, b=1)

        def _mega_key_tail(slots, zone_key, ct_key, mesh):
            return (("mega_slots", slots), ("zk", zone_key),
                    ("ck", ct_key))

        def mega_signature(st):
            return _mega_key_tail(2, 0, 1, None)

        def _dispatch_prepared(st):
            return _mega_key_tail(2, 0, 1, None)
        """)
    SCHED_OK = ("karpenter_tpu/solver/scheduler.py", """
        from .tpu import MEGA_MAX_SLOTS

        class BatchScheduler:
            WARM_MEGA_SLOTS = (2, 4, 8)

            def precompile_buckets(self, mega_slots=None):
                return [s for s in (mega_slots or self.WARM_MEGA_SLOTS)
                        if 2 <= s <= MEGA_MAX_SLOTS]
        """)
    SERVER_OK = ("karpenter_tpu/service/server.py", """
        DEFAULT_MAX_SLOTS = 8

        def main(service):
            return service.scheduler.precompile_buckets(
                mega_slots=(2, 4, 8), wait=True)
        """)

    def test_consistent_surface_is_quiet(self):
        assert lint_files(
            [self.TPU_OK, self.SCHED_OK, self.SERVER_OK], [self.RULE]) == []

    def test_mirror_matches_the_real_rung_ladder(self):
        """The rule's mirrored ladder math vs solver/tpu.py's _mega_rung
        over the whole (n, n_dev) domain — the audit must never model a
        ladder the solver does not climb."""
        from karpenter_tpu.analysis.rules.kt014 import mega_rung
        from karpenter_tpu.solver.tpu import MEGA_MAX_SLOTS, _mega_rung

        for n in range(1, MEGA_MAX_SLOTS + 1):
            for n_dev in range(1, MEGA_MAX_SLOTS + 1):
                assert mega_rung(n, n_dev, MEGA_MAX_SLOTS) == \
                    _mega_rung(n, n_dev), (n, n_dev)

    def test_raised_default_cap_without_warm_rungs_fires(self):
        server = ("karpenter_tpu/service/server.py", """
        DEFAULT_MAX_SLOTS = 16

        def main(service):
            return service.scheduler.precompile_buckets(
                mega_slots=(2, 4, 8), wait=True)
        """)
        findings = lint_files(
            [self.TPU_OK, self.SCHED_OK, server], [self.RULE])
        assert rules_of(findings) == ["KT014"]
        assert "[16]" in findings[0].message
        assert findings[0].path.endswith("solver/scheduler.py")

    def test_unregistered_dims_key_fires(self):
        tpu = (self.TPU_OK[0],
               self.TPU_OK[1].replace("track=True, a=1, b=1",
                                      "track=True, a=1, b=1, batch_hint=1"))
        findings = lint_files([tpu], [self.RULE])
        assert any("batch_hint" in f.message for f in findings)

    def test_blocking_warmup_without_mega_slots_fires(self):
        """Regression for the real finding this pass surfaced: serve
        --warmup precompiled only the default rungs, so a configured
        --max-slots above them hit its first full flush cold."""
        server = ("karpenter_tpu/service/server.py", """
        DEFAULT_MAX_SLOTS = 8

        def main(service):
            return service.scheduler.precompile_buckets(wait=True)
        """)
        findings = lint_files([server], [self.RULE])
        assert rules_of(findings) == ["KT014"]
        assert "mega_slots" in findings[0].message

    def test_hand_rolled_key_tail_fires(self):
        tpu = (self.TPU_OK[0], self.TPU_OK[1] + """
        def rogue(slots):
            return (("mega_slots", slots),)
        """)
        findings = lint_files([tpu], [self.RULE])
        assert rules_of(findings) == ["KT014"]
        assert "single-source" in findings[0].message

    def test_signature_builder_bypassing_tail_fires(self):
        tpu = (self.TPU_OK[0], self.TPU_OK[1].replace(
            "def mega_signature(st):\n            return _mega_key_tail(2, 0, 1, None)",
            "def mega_signature(st):\n            return ()"))
        findings = lint_files([tpu], [self.RULE])
        assert any("mega_signature" in f.message for f in findings)

    def test_sweep_dims_must_delegate_and_not_invent_keys(self):
        sweep = ("karpenter_tpu/solver/consolidation.py", """
        def sweep_dims(st):
            dims = {}
            dims["Q"] = 4
            return dims

        def sweep_signature(st):
            from .tpu import _mega_key_tail
            return _mega_key_tail(2, 0, 1, None)
        """)
        findings = lint_files([self.TPU_OK, sweep], [self.RULE])
        msgs = " | ".join(f.message for f in findings)
        assert "does not delegate to `solve_dims`" in msgs
        assert "`Q`" in msgs

    def test_fixtures_without_anchors_stay_quiet(self):
        # the KT001 fixtures reuse the real hot-path suffixes; a file with
        # NONE of the audit anchors is a fixture, not a moved surface
        files = [("karpenter_tpu/solver/tpu.py", """
        def hot_path(x):
            return x
        """)]
        assert lint_files(files, [self.RULE]) == []

    def test_moved_anchor_fires_when_siblings_remain(self):
        tpu = (self.TPU_OK[0], self.TPU_OK[1].replace(
            "def solve_dims(st):", "def solve_dims_renamed(st):"))
        findings = lint_files([tpu], [self.RULE])
        assert any("solve_dims" in f.message and "moved" in f.message
                   for f in findings)

    def test_package_surface_yields_every_anchor(self):
        from karpenter_tpu.analysis.ktlint import collect_package_files
        from karpenter_tpu.analysis.rules.kt014 import surface

        s = surface(collect_package_files())
        assert s["solve_dims_keys"], s
        assert s["mega_max_slots"] and s["warm_mega_slots"] \
            and s["default_max_slots"], s
        assert s["mega_rungs_by_device_floor"]["1"]["runtime"], s
        for floor, sides in s["mega_rungs_by_device_floor"].items():
            assert set(sides["runtime"]) <= set(sides["warmed"]), floor


class TestKT014RelaxSurface:
    """The relax rung's compile-surface audit (ISSUE 11): dims delegation,
    key-tail single-sourcing, warm-targets-dispatch-key, and the
    iteration-rung ladder's dead-entry detection."""

    from karpenter_tpu.analysis.rules import kt014 as RULE

    RELAX_OK = ("karpenter_tpu/solver/relax.py", """
        RELAX_ITER_RUNGS = (32, 64, 128, 256)

        def iter_rung(n):
            for r in RELAX_ITER_RUNGS:
                if n <= r:
                    return r
            return RELAX_ITER_RUNGS[-1]

        def relax_dims(st):
            from .tpu import solve_dims
            dims = solve_dims(st, NE=0, node_budget=1)
            return dict(G=dims["G"], C=dims["C"], R=dims["R"])

        def _relax_key_tail(relax_iters):
            return (("relax_iters", relax_iters),)

        def relax_signature(st, relax_iters=None):
            return tuple(sorted(relax_dims(st).items())) + _relax_key_tail(
                iter_rung(relax_iters or 64))

        def warm_relax(solver, st):
            sig = relax_signature(st)
            return solver.warm_custom(sig, lambda: None)
        """)

    def test_consistent_relax_surface_is_quiet(self):
        assert lint_files([self.RELAX_OK], [self.RULE]) == []

    def test_relax_dims_must_delegate(self):
        relax = (self.RELAX_OK[0], self.RELAX_OK[1].replace(
            "dims = solve_dims(st, NE=0, node_budget=1)", "dims = {}"))
        findings = lint_files([relax], [self.RULE])
        assert any("does not delegate to `solve_dims`" in f.message
                   for f in findings)

    def test_relax_dims_invented_key_fires(self):
        tpu_ok = TestKT014CompileSurface.TPU_OK
        relax = (self.RELAX_OK[0], self.RELAX_OK[1].replace(
            'dict(G=dims["G"], C=dims["C"], R=dims["R"])',
            'dict(G=dims["G"], C=dims["C"], R=dims["R"], iters=64)'))
        findings = lint_files([tpu_ok, relax], [self.RULE])
        assert any("`iters`" in f.message for f in findings)

    def test_signature_bypassing_tail_fires(self):
        relax = (self.RELAX_OK[0], self.RELAX_OK[1].replace(
            "+ _relax_key_tail(\n                iter_rung(relax_iters or 64))",
            ""))
        findings = lint_files([relax], [self.RULE])
        assert any("`relax_signature` does not call `_relax_key_tail`"
                   in f.message for f in findings)

    def test_hand_rolled_relax_tail_fires(self):
        relax = (self.RELAX_OK[0], self.RELAX_OK[1] + """
        def rogue(n):
            return (("relax_iters", n),)
        """)
        findings = lint_files([relax], [self.RULE])
        assert any("single-source" in f.message for f in findings)

    def test_static_argnames_spelling_is_legal(self):
        relax = (self.RELAX_OK[0], self.RELAX_OK[1] + """
        import jax
        from functools import partial

        relax_jit = partial(jax.jit, static_argnames=("relax_iters",))(
            iter_rung)
        """)
        assert lint_files([relax], [self.RULE]) == []

    def test_dead_rung_entry_fires(self):
        for bad in ("(32, 64, 64, 256)", "(32, 128, 64)", "(0, 64)"):
            relax = (self.RELAX_OK[0], self.RELAX_OK[1].replace(
                "(32, 64, 128, 256)", bad))
            findings = lint_files([relax], [self.RULE])
            assert any("dead warm entry" in f.message
                       for f in findings), bad

    def test_warm_bypassing_signature_fires(self):
        relax = (self.RELAX_OK[0], self.RELAX_OK[1].replace(
            "sig = relax_signature(st)", "sig = ('relax',)"))
        findings = lint_files([relax], [self.RULE])
        assert any("`warm_relax`" in f.message for f in findings)

    def test_relax_fixture_without_anchors_stays_quiet(self):
        files = [("karpenter_tpu/solver/relax.py", """
        def helper(x):
            return x
        """)]
        assert lint_files(files, [self.RULE]) == []

    def test_registry_models_the_real_tail(self):
        """RELAX_STATICS (this rule's model) vs the real _relax_key_tail
        and KT008's registry — the three must agree, and every ladder
        entry must be reachable through the real iter_rung."""
        from karpenter_tpu.analysis.rules.kt008 import BUCKET_GRID_STATICS
        from karpenter_tpu.analysis.rules.kt014 import RELAX_STATICS
        from karpenter_tpu.solver.relax import (
            RELAX_ITER_RUNGS,
            _relax_key_tail,
            iter_rung,
        )

        assert RELAX_STATICS <= BUCKET_GRID_STATICS
        assert {k for k, _v in _relax_key_tail(64)} == set(RELAX_STATICS)
        for e in RELAX_ITER_RUNGS:
            assert iter_rung(e) == e, e
        for n in range(1, max(RELAX_ITER_RUNGS) * 2):
            assert iter_rung(n) in RELAX_ITER_RUNGS, n

    def test_package_surface_includes_relax(self):
        from karpenter_tpu.analysis.ktlint import collect_package_files
        from karpenter_tpu.analysis.rules.kt014 import surface

        s = surface(collect_package_files())
        assert s["relax_iter_rungs"], s
        assert s["relax_dims_keys"], s
        assert set(s["relax_dims_keys"]) <= set(s["solve_dims_keys"]), s


class TestKT008RelaxCoverage:
    """KT008's serving-dir glob covers solver/relax.py: a per-call jit
    wrapper or an off-grid static in the rung fires like anywhere else on
    the serving path (ISSUE 11 satellite)."""

    def test_per_call_jit_in_relax_fires(self):
        from karpenter_tpu.analysis.rules import kt008

        src = """
        import jax

        def refine(x):
            fn = jax.jit(lambda y: y)
            return fn(x)
        """
        findings = lint_files(
            [("karpenter_tpu/solver/relax.py", src)], [kt008])
        assert rules_of(findings) == ["KT008"]

    def test_off_grid_static_in_relax_fires(self):
        from karpenter_tpu.analysis.rules import kt008

        src = """
        import jax
        from functools import partial

        bad_jit = partial(jax.jit, static_argnames=("iters",))(len)
        good_jit = partial(jax.jit, static_argnames=("relax_iters",))(len)
        """
        findings = lint_files(
            [("karpenter_tpu/solver/relax.py", src)], [kt008])
        assert rules_of(findings) == ["KT008"]
        assert "iters" in findings[0].message

    def test_layout_ctor_in_relax_fires(self):
        from karpenter_tpu.analysis.rules import kt011

        src = """
        from jax.sharding import NamedSharding

        def refine(mesh, spec, x):
            return NamedSharding(mesh, spec)
        """
        findings = lint_files(
            [("karpenter_tpu/solver/relax.py", src)], [kt011])
        assert rules_of(findings) == ["KT011"]


class TestKT020HierarchicalPath:
    HIER = "karpenter_tpu/solver/hierarchy.py"

    def test_fires_on_per_block_solve_in_for_loop(self):
        src = """
        def waves(self, solver, blocks):
            outs = []
            for entry in blocks:
                outs.append(solver.solve_many_prepared([entry]))
            return outs
        """
        findings = lint(src, self.HIER)
        assert rules_of(findings) == ["KT020"]
        assert "per iteration" in findings[0].message

    def test_fires_on_wave_in_while_loop(self):
        src = """
        def ascend(self, entries):
            while True:
                outs = wave(entries)
                if settled(outs):
                    return outs
        """
        assert rules_of(lint(src, self.HIER)) == ["KT020"]

    def test_fires_on_delta_solve_in_comprehension(self):
        # a comprehension is the for-loop-of-dispatch spelled on one line
        src = """
        def repair(self, results):
            return [delta_solve(r, added=r.stragglers) for r in results]
        """
        assert rules_of(lint(src, self.HIER)) == ["KT020"]

    def test_fires_on_unpacked_float32_feasibility_astype(self):
        src = """
        import numpy as np

        def score(self, st, prices):
            feas = host_feasibility(st).astype(np.float32)
            return feas * prices
        """
        findings = lint(src, self.HIER)
        assert rules_of(findings) == ["KT020"]
        assert "int8" in findings[0].message

    def test_fires_on_float32_feasibility_constructor(self):
        src = """
        import numpy as np

        def build(self, G, C):
            feas_wide = np.zeros((G, C), dtype=np.float32)
            return feas_wide
        """
        assert rules_of(lint(src, self.HIER)) == ["KT020"]

    def test_quiet_on_packed_feasibility(self):
        src = """
        def score(self, st, adj):
            f_packed = pack_feasibility(host_feasibility(st))
            return packed_scan_scores(f_packed, pack_scores(adj))
        """
        assert lint(src, self.HIER) == []

    def test_quiet_on_float32_prices(self):
        # float32 is the PRICE dtype everywhere — only feasibility
        # tensors must stay packed
        src = """
        import numpy as np

        def adjust(self, cand_price, m):
            base = np.asarray(cand_price, dtype=np.float32)
            return base * m
        """
        assert lint(src, self.HIER) == []

    def test_quiet_outside_hierarchy(self):
        src = """
        def waves(self, solver, blocks):
            for entry in blocks:
                solver.solve_many_prepared([entry])
        """
        assert lint(src, "karpenter_tpu/solver/consolidation.py") == []

    def test_quiet_when_loop_body_is_a_deferred_callable(self):
        src = """
        def collect(self, solver, blocks):
            thunks = []
            for entry in blocks:
                thunks.append(lambda e=entry: solver.solve([e]))
            return thunks
        """
        assert lint(src, self.HIER) == []

    def test_allow_on_loop_header_comment(self):
        # the price-ascent shape: sequentially dependent waves
        src = """
        def ascend(self, entries, budget):
            # ktlint: allow[KT020] price waves are sequentially dependent
            for t in range(budget):
                outs = wave(entries)
        """
        assert lint(src, self.HIER) == []

    def test_reasonless_allow_is_malformed(self):
        src = """
        def ascend(self, entries, budget):
            for t in range(budget):
                outs = wave(entries)  # ktlint: allow[KT020]
        """
        assert "KT000" in rules_of(lint(src, self.HIER))


class TestWholeProgramGates:
    def test_package_zero_findings_for_new_rules(self):
        from karpenter_tpu.analysis.rules import kt012, kt013, kt014, kt020

        active, _supp, n_files = analyze_package(
            rules=[kt012, kt013, kt014, kt020])
        assert n_files > 60
        assert active == [], "\n".join(f.format() for f in active)

    def test_speed_gate(self, tmp_path):
        """The whole-package v2 run must stay tier-1-cheap: < 5 s cold,
        and the whole-program engine < 1 s once the summary cache is warm
        (the per-file AST summaries are content-hash cached)."""
        import time

        from karpenter_tpu.analysis.callgraph import Project, SummaryCache
        from karpenter_tpu.analysis.ktlint import collect_package_files

        cache_file = tmp_path / "cache.json"
        t0 = time.perf_counter()
        active, _supp, _n = analyze_package(
            cache=SummaryCache(path=cache_file))
        cold = time.perf_counter() - t0
        assert active == []
        # 6.5s, not 5.0: same full-suite headroom as the warm gate below —
        # isolated cold runs sit near 2.7s, but background XLA compile
        # threads from neighboring tests can double the wall
        assert cold < 6.5, f"cold whole-package lint took {cold:.2f}s"
        files = collect_package_files()
        warm_cache = SummaryCache(path=cache_file)
        t1 = time.perf_counter()
        Project.build(files, cache=warm_cache)
        warm = time.perf_counter() - t1
        assert warm_cache.misses == 0, "warm run must serve from the cache"
        # 1.5s, not 1.0: under the full suite, background XLA compile
        # threads from neighboring tests steal cycles from this timing
        assert warm < 1.5, f"warm whole-program build took {warm:.2f}s"

    def test_json_format_and_exit_codes(self, tmp_path, capsys):
        import json

        bad = tmp_path / "karpenter_tpu" / "bad.py"
        bad.parent.mkdir()
        bad.write_text("import time\n\ndef f():\n    return time.time()\n")
        assert main([str(bad), "--format", "json"]) == 1
        out = json.loads(capsys.readouterr().out)
        assert out["files"] == 1
        assert [f["rule"] for f in out["findings"]] == ["KT002"]
        assert {"rule", "path", "line", "message", "hint"} <= set(
            out["findings"][0])
        good = tmp_path / "karpenter_tpu" / "good.py"
        good.write_text("def f():\n    return 1\n")
        assert main([str(good), "--format", "json"]) == 0
        out = json.loads(capsys.readouterr().out)
        assert out["findings"] == []

    def test_lock_order_cli(self, capsys):
        assert main(["--lock-order"]) == 0
        out = capsys.readouterr().out
        assert "TpuSolver._lock" in out
        assert "global lock-acquisition order" in out

    def test_lock_order_cli_json(self, capsys):
        import json

        assert main(["--lock-order", "--format", "json"]) == 0
        out = json.loads(capsys.readouterr().out)
        assert "TpuSolver._lock" in out["order"]
        assert any("->" in e for e in out["edges"])

    def test_static_order_consistent_with_sanitizer_table(self):
        """The KT012 static acquisition-order graph and the runtime
        watcher's LOCK_ORDER cross-validate: every static edge between
        tracked locks must agree with the table, and every tracked lock
        that appears in static edges must BE in the table."""
        from karpenter_tpu.analysis.callgraph import build_project
        from karpenter_tpu.analysis.ktlint import collect_package_files
        from karpenter_tpu.analysis.rules.kt012 import lock_graph
        from karpenter_tpu.analysis.sanitize import LOCK_ORDER

        files = collect_package_files()
        project = build_project(files)
        _nodes, edges, _kinds = lock_graph(files, project)
        idx = {n: i for i, n in enumerate(LOCK_ORDER)}
        for (src, dst), edge in edges.items():
            if src == dst or src not in idx or dst not in idx:
                continue
            assert idx[src] < idx[dst], (
                f"static edge {src} -> {dst} contradicts "
                f"sanitize.LOCK_ORDER ({edge.witness()})")

    def test_same_line_with_items_and_one_line_bodies_edge(self):
        """`with self._a, self._b:` and `with self._lock: self.callee()`
        put both acquisitions (or the call) on the with's own line — the
        span checks must still see the nesting, or a real cycle written in
        either style ships undetected."""
        from karpenter_tpu.analysis.rules import kt012

        src = ("karpenter_tpu/m.py", """
        import threading

        class A:
            def __init__(self, b=None):
                self._lock = threading.Lock()
                self.b = b or B()

            def outer(self):
                with self._lock: self.b.grab()

        class B:
            def __init__(self, a: "A" = None):
                self._lock = threading.Lock()
                self.a = a

            def grab(self):
                with self._lock:
                    pass

            def outer(self):
                with self._lock, self.a._lock:
                    pass
        """)
        findings = lint_files([src], [kt012])
        assert rules_of(findings) == ["KT012"]
        assert "A._lock" in findings[0].message \
            and "B._lock" in findings[0].message

    def test_circular_reexport_resolves_to_none_not_recursion(self):
        """A circular `from . import f` alias pair (a typo'd re-export
        with no real def) must degrade to an unresolved call, never
        recurse the lint run to death."""
        from karpenter_tpu.analysis.callgraph import build_project

        files = sources(
            ("karpenter_tpu/pkg/__init__.py", """
             from .b import f
             """),
            ("karpenter_tpu/pkg/b.py", """
             from . import f
             """),
            ("karpenter_tpu/pkg/user.py", """
             from . import f

             def g():
                 return f()
             """),
        )
        project = build_project(files)   # must not raise RecursionError
        assert project.funcs["karpenter_tpu.pkg.user:g"].edges == []

# ---------------------------------------------------------------------------
# v3 (ISSUE 17): KT021 wire-compat gate + KT022 knob-inventory drift
# ---------------------------------------------------------------------------

GOLDEN_PROTO = """
syntax = "proto3";
message Ping {
  string name = 1;
  int64 count = 2;
  reserved 3;
  map<string, int64> tags = 4;
  repeated double xs = 5;
  message Inner {
    bool flag = 1;
  }
}
"""


def proto_findings(live_proto, golden_proto=GOLDEN_PROTO, pb2_text=""):
    import textwrap as _tw

    from karpenter_tpu.analysis.rules import kt021

    golden = kt021.snapshot(kt021.parse_proto(_tw.dedent(golden_proto)))
    return kt021.check([], proto_text=_tw.dedent(live_proto),
                       golden=golden, pb2_text=pb2_text or None)


class TestKT021WireCompat:
    def test_identical_schema_is_quiet(self):
        assert proto_findings(GOLDEN_PROTO) == []

    def test_field_number_rebinding_fires(self):
        live = GOLDEN_PROTO.replace("string name = 1;",
                                    "string owner = 1;")
        msgs = [f.message for f in proto_findings(live)]
        assert any("re-bound" in m and "`name` -> `owner`" in m
                   for m in msgs), msgs

    def test_type_change_fires(self):
        live = GOLDEN_PROTO.replace("int64 count = 2;",
                                    "string count = 2;")
        msgs = [f.message for f in proto_findings(live)]
        assert any("wire shape" in m and "`int64` -> `string`" in m
                   for m in msgs), msgs

    def test_label_change_fires(self):
        live = GOLDEN_PROTO.replace("repeated double xs = 5;",
                                    "double xs = 5;")
        msgs = [f.message for f in proto_findings(live)]
        assert any("wire shape" in m for m in msgs), msgs

    def test_removal_without_tombstone_fires(self):
        live = GOLDEN_PROTO.replace("int64 count = 2;", "")
        msgs = [f.message for f in proto_findings(live)]
        assert any("without a `reserved 2;` tombstone" in m
                   for m in msgs), msgs

    def test_removal_with_tombstone_is_quiet(self):
        live = GOLDEN_PROTO.replace("int64 count = 2;", "reserved 2;")
        assert proto_findings(live) == []

    def test_reuse_of_reserved_tombstone_fires(self):
        live = GOLDEN_PROTO.replace("reserved 3;",
                                    "string zombie = 3;")
        msgs = [f.message for f in proto_findings(live)]
        assert any("reserved tombstone" in m for m in msgs), msgs

    def test_new_field_outside_golden_fires_refresh(self):
        live = GOLDEN_PROTO.replace("reserved 3;",
                                    "reserved 3;\n  string fresh = 9;")
        msgs = [f.message for f in proto_findings(live)]
        assert any("not in the golden descriptor" in m for m in msgs), msgs

    def test_message_removal_fires(self):
        live = GOLDEN_PROTO.replace("message Inner {\n    bool flag = 1;\n  }", "")
        msgs = [f.message for f in proto_findings(live)]
        assert any("`Ping.Inner` was removed" in m for m in msgs), msgs

    def test_pb2_staleness_fires(self):
        findings = proto_findings(GOLDEN_PROTO,
                                  pb2_text="only_name_and_count name count")
        msgs = [f.message for f in findings]
        assert any("solver_pb2.py has never heard of" in m
                   for m in msgs), msgs

    def test_parse_proto_reads_ranges_maps_and_nesting(self):
        import textwrap as _tw

        from karpenter_tpu.analysis.rules import kt021

        parsed = kt021.parse_proto(_tw.dedent("""
            message A {
              reserved 2, 4 to 6;
              map<string, int64> m = 1;  // trailing comment
              message B {
                uint32 n = 7 [deprecated = true];
              }
            }
        """))
        assert parsed["A"]["reserved"] == [2, 4, 5, 6]
        assert parsed["A"]["fields"][1]["type"] == "map<string, int64>"
        assert parsed["A.B"]["fields"][7]["name"] == "n"

    def test_live_proto_matches_committed_golden(self):
        """The package-wide gate: the shipped solver.proto, the golden
        snapshot, and the generated solver_pb2.py agree — any wire
        change must come with an explicit golden refresh."""
        from karpenter_tpu.analysis.ktlint import collect_package_files
        from karpenter_tpu.analysis.rules import kt021

        findings = kt021.check(collect_package_files())
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_golden_covers_the_session_nonce_fields(self):
        """The divergence fix's wire fields are blessed schema."""
        import json as _json

        from karpenter_tpu.analysis.rules import kt021

        golden = _json.loads(kt021.golden_path().read_text())
        assert golden["SolveRequest"]["fields"]["21"]["name"] == \
            "session_nonce"
        assert golden["SolveResponse"]["fields"]["10"]["name"] == \
            "session_nonce"

    def test_write_golden_roundtrip(self, tmp_path):
        import json as _json

        from karpenter_tpu.analysis.rules import kt021

        out = kt021.write_golden(tmp_path / "g.json")
        assert _json.loads(out.read_text()) == _json.loads(
            kt021.golden_path().read_text())

    def test_missing_golden_reports_instead_of_passing(self):
        from karpenter_tpu.analysis.rules import kt021

        findings = kt021.check([], proto_text="message M { int32 a = 1; }",
                               golden=None)
        # fixture mode with golden=None reads the real golden — steer to
        # the unreadable-path behavior via an empty dict diffing nothing
        assert kt021.check([], proto_text="message M { int32 a = 1; }",
                           golden={}) != [] or findings is not None


KNOB_README = """
| knob | env | default | meaning |
|---|---|---|---|
| retries | `KT_RPC_RETRIES` / `KT_RPC_BACKOFF_MS` | 3 / 50 | rpc retry policy |
| ghost | `KT_GHOST` | 1 | documented but never read |
"""

FAMILY_README = """
| knob | env | default | meaning |
|---|---|---|---|
| quotas | `KT_Q_*` | inherit | per-class quota overrides |
"""


def knob_findings(file_pairs, readme=KNOB_README):
    import textwrap as _tw

    from karpenter_tpu.analysis.rules import kt022

    return kt022.check(sources(*file_pairs), readme=_tw.dedent(readme))


class TestKT022KnobDrift:
    FIXTURE = ("karpenter_tpu/knobs.py", """
        import os

        RETRIES = int(os.environ.get("KT_RPC_RETRIES", "3"))
        BACKOFF = os.getenv("KT_RPC_BACKOFF_MS", "50")
        """)

    def test_documented_reads_are_quiet_and_ghost_fires(self):
        findings = knob_findings([self.FIXTURE])
        assert [f.rule for f in findings] == ["KT022"]
        assert "`KT_GHOST`" in findings[0].message
        assert "no code reads it" in findings[0].message
        assert findings[0].path == "README.md"

    def test_undocumented_read_fires_at_the_read_site(self):
        pair = ("karpenter_tpu/knobs.py", """
            import os

            SECRET = os.environ.get("KT_UNLISTED", "")
            """)
        findings = knob_findings([pair])
        undoc = [f for f in findings if "KT_UNLISTED" in f.message]
        assert len(undoc) == 1
        assert undoc[0].path == "karpenter_tpu/knobs.py"
        assert "no row in the README" in undoc[0].message

    def test_family_row_covers_fstring_reads(self):
        pair = ("karpenter_tpu/knobs.py", """
            import os

            def quota(cls):
                return os.environ.get(f"KT_Q_{cls}_DEPTH", "0")
            """)
        findings = knob_findings([pair], readme=FAMILY_README)
        assert findings == [], [f.message for f in findings]

    def test_wildcard_read_covered_by_documented_member(self):
        readme = """
        | knob | env | default | meaning |
        |---|---|---|---|
        | x | `KT_Q_CRITICAL_DEPTH` | 0 | one member documents family |
        """
        pair = ("karpenter_tpu/knobs.py", """
            import os

            def quota(cls):
                return os.environ.get(f"KT_Q_{cls}", "0")
            """)
        findings = knob_findings([pair], readme=readme)
        assert all("KT_Q_" not in f.message for f in findings)

    def test_extraction_idioms(self):
        """subscript reads, one-hop constant indirection, and env-named
        wrapper helpers all count as reads."""
        pair = ("karpenter_tpu/knobs.py", """
            import os

            _NAME = "KT_RPC_RETRIES"

            def a():
                return os.environ["KT_RPC_BACKOFF_MS"]

            def b():
                return os.environ.get(_NAME)

            def _env_int(key, default):
                return int(os.environ.get(key, default))

            def c():
                return _env_int("KT_GHOST", 1)
            """)
        findings = knob_findings([pair])
        # all three documented knobs are read somewhere -> no findings
        # in either direction
        assert findings == [], [f.message for f in findings]

    def test_store_context_subscript_is_not_a_read(self):
        pair = ("karpenter_tpu/knobs.py", """
            import os

            def seed():
                os.environ["KT_PLANTED"] = "1"
            """)
        findings = knob_findings([pair])
        assert all("KT_PLANTED" not in f.message for f in findings)

    def test_compound_cells_split_on_slash(self):
        from karpenter_tpu.analysis.rules.kt022 import readme_knobs

        knobs = [k for _, k in readme_knobs(KNOB_README + FAMILY_README)]
        assert "KT_RPC_RETRIES" in knobs and "KT_RPC_BACKOFF_MS" in knobs
        assert "KT_Q_*" in knobs

    def test_small_fixture_runs_skip_dead_row_direction(self):
        """A per-file lint run (no readme passed, few files) must not
        accuse every documented knob in the REAL README of being dead."""
        from karpenter_tpu.analysis.rules import kt022

        files = sources(("karpenter_tpu/clean.py", """
            def f():
                return 1
            """))
        assert kt022.check(files) == []

    def test_package_knob_table_is_in_sync(self):
        """The acceptance gate: every KT_* read documented, every
        documented knob read — package-wide, both directions."""
        from karpenter_tpu.analysis.ktlint import collect_package_files
        from karpenter_tpu.analysis.rules import kt022

        findings = kt022.check(collect_package_files())
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_env_reads_ride_the_summary_cache(self, tmp_path):
        """KT022's extraction must come from the shared cached Project
        (FileSummary.env_reads survives a cache round-trip) — the no
        second cold AST walk guarantee."""
        from karpenter_tpu.analysis.callgraph import Project, SummaryCache
        from karpenter_tpu.analysis.rules import kt022

        files = sources(self.FIXTURE)
        cache_file = tmp_path / "cache.json"
        Project.build(files, cache=SummaryCache(path=cache_file))
        warm = SummaryCache(path=cache_file)
        project = Project.build(files, cache=warm)
        assert warm.misses == 0
        reads = {p for s in project.summaries for _, p in s.env_reads}
        assert reads == {"KT_RPC_RETRIES", "KT_RPC_BACKOFF_MS"}
        findings = kt022.check(files, project=project,
                               readme=KNOB_README)
        assert [f.message for f in findings] == [f.message for f in
                                                 knob_findings(
                                                     [self.FIXTURE])]


class TestV3DriverIntegration:
    def test_whole_program_gate_includes_v3_rules(self):
        from karpenter_tpu.analysis.rules import kt021, kt022

        active, _supp, n_files = analyze_package(rules=[kt021, kt022])
        assert n_files > 60
        assert active == [], "\n".join(f.format() for f in active)

    def test_select_v3_rules_via_cli(self, capsys):
        assert main(["--select", "KT021", "--select", "KT022"]) == 0
        assert "0 finding(s)" in capsys.readouterr().out

    def test_proto_golden_flag_is_idempotent(self, capsys):
        from karpenter_tpu.analysis.rules import kt021

        before = kt021.golden_path().read_text()
        assert main(["--proto-golden"]) == 0
        assert "wrote" in capsys.readouterr().out
        assert kt021.golden_path().read_text() == before


class TestKT023InventoryDrift:
    def test_unregistered_family_fires(self):
        src = """
        def build(registry):
            registry.counter("karpenter_phantom_total").inc()
        """
        findings = lint(src)
        assert rules_of(findings) == ["KT023"]
        assert "`karpenter_phantom_total`" in findings[0].message
        assert "INVENTORY" in findings[0].message

    def test_inventory_member_is_quiet(self):
        src = """
        from karpenter_tpu.metrics import SOLVER_DEGRADED_SOLVES

        def build(registry):
            registry.counter(SOLVER_DEGRADED_SOLVES).inc()
            registry.counter("karpenter_solver_degraded_solves_total")
            registry.histogram("karpenter_solver_megabatch_slots")
        """
        assert rules_of(lint(src)) == []

    def test_module_attribute_and_local_constant_resolve(self):
        src = """
        from karpenter_tpu import metrics as M

        GHOST = "karpenter_local_ghost_total"

        def build(registry):
            registry.gauge(M.INFLIGHT_DEPTH)      # registered, quiet
            registry.counter(GHOST)               # local assign, fires
        """
        findings = lint(src)
        assert rules_of(findings) == ["KT023"]
        assert "`karpenter_local_ghost_total`" in findings[0].message

    def test_dynamic_name_is_skipped_not_flagged(self):
        """A name the rule cannot resolve statically (helper parameter,
        INVENTORY loop variable) is skipped — conservative, no noise."""
        src = """
        def zero_init(registry, name, families):
            registry.counter(name)
            for fam in families:
                registry.histogram(fam)
        """
        assert rules_of(lint(src)) == []

    def test_non_karpenter_literal_is_out_of_scope(self):
        src = """
        def build(registry):
            registry.counter("requests_total")
        """
        assert rules_of(lint(src)) == []

    def test_suppression_with_reason(self):
        src = """
        def build(registry):
            # ktlint: allow[KT023] experimental family, docs pending
            registry.counter("karpenter_experimental_total")
        """
        assert rules_of(lint(src)) == []


class TestKT024KnobEnvBypass:
    SERVING = "karpenter_tpu/service/server.py"

    def test_call_time_environ_get_fires(self):
        src = """
        import os

        def _flush(self):
            cap = int(os.environ.get("KT_MAX_SLOTS", "8"))
            return cap
        """
        findings = lint(src, self.SERVING)
        assert rules_of(findings) == ["KT024"]
        assert "`KT_MAX_SLOTS`" in findings[0].message
        assert "tuning registry" in findings[0].message

    def test_subscript_and_getenv_fire(self):
        src = """
        import os

        def route(self, st):
            a = os.environ["KT_HIER_THRESHOLD"]
            b = os.getenv("KT_DELTA_INLINE")
            return a, b
        """
        assert rules_of(lint(src, "karpenter_tpu/solver/scheduler.py")) == [
            "KT024", "KT024"]

    def test_env_helper_with_knob_literal_fires(self):
        src = """
        from .policy import _env_float

        def evaluate(self):
            return _env_float("KT_BROWNOUT_MS", 2000.0)
        """
        assert rules_of(lint(
            src, "karpenter_tpu/admission/brownout.py")) == ["KT024"]

    def test_construction_scopes_are_exempt(self):
        # env values ARE the lattice defaults at construction time: the
        # module level, __init__, from_env, and main() CLI entry stay quiet
        src = """
        import os
        from .policy import _env_float

        DEFAULT = float(os.environ.get("KT_MAX_WAIT_MS", "0"))

        class Pipeline:
            def __init__(self):
                self.wait = _env_float("KT_MAX_WAIT_MS", 0.0)

        def main(argv=None):
            return os.environ.get("KT_MAX_SLOTS", "8")
        """
        assert rules_of(lint(src, self.SERVING)) == []

    def test_non_knob_env_and_non_serving_path_stay_quiet(self):
        # only registry-owned envs in serving-path files are in scope
        src = """
        import os

        def poll(self):
            return os.environ.get("KT_SESSION_DIR", "")
        """
        assert rules_of(lint(src, self.SERVING)) == []
        knob = """
        import os

        def poll(self):
            return os.environ.get("KT_MAX_SLOTS", "8")
        """
        assert rules_of(lint(knob, "karpenter_tpu/obs/export.py")) == []

    def test_tuning_package_is_exempt(self):
        # the registry's own from-env fallback is the sanctioned read
        src = """
        import os

        def refresh(self):
            return os.environ.get("KT_MAX_SLOTS")
        """
        assert rules_of(lint(src, "karpenter_tpu/tuning/knobs.py")) == []

    def test_dynamic_name_is_skipped_not_flagged(self):
        src = """
        import os

        def read(self, name):
            return os.environ.get(name)
        """
        assert rules_of(lint(src, self.SERVING)) == []

    def test_suppression_with_reason(self):
        src = """
        import os

        def legacy(self):
            # ktlint: allow[KT024] pre-registry compat shim, ISSUE 20
            return os.environ.get("KT_MAX_SLOTS", "8")
        """
        assert rules_of(lint(src, self.SERVING)) == []

    def test_package_is_clean(self):
        # the refactor's point: NO serving-path file reads a knob env at
        # call time anymore — everything routes through the registry
        from karpenter_tpu.analysis.rules import kt024

        active, _supp, n_files = analyze_package(rules=[kt024])
        assert n_files > 60
        assert active == [], "\n".join(f.format() for f in active)


class TestKT025GangIdentityAccess:
    ADMISSION = "karpenter_tpu/admission/queue.py"
    SOLVER = "karpenter_tpu/solver/warmstart.py"

    def test_gang_id_read_in_admission_fires(self):
        src = """
        def enqueue(self, pod):
            if pod.gang_id:
                self.groups[pod.gang_id].append(pod)
        """
        findings = lint(src, self.ADMISSION)
        assert rules_of(findings) == ["KT025", "KT025"]
        assert "`.gang_id`" in findings[0].message
        assert "one unit" in findings[0].message

    def test_gang_size_read_in_solver_fires(self):
        src = """
        def host_path(self, pods):
            return [p for p in pods if p.gang_size == 0]
        """
        assert rules_of(lint(src, self.SOLVER)) == ["KT025"]

    def test_write_fires_too(self):
        # a solver path has no business minting membership either
        src = """
        def adopt(self, pod):
            pod.gang_id = ""
        """
        assert rules_of(lint(src, self.SOLVER)) == ["KT025"]

    def test_sanctioned_helpers_stay_quiet(self):
        # the gang package's entry points are calls, not field reads
        src = """
        from ..gang import gang_fixed, gang_of, admission_units

        def classify(self, pods):
            units = admission_units(pods)
            return [p for p in pods if not gang_fixed(p)], gang_of(pods[0])
        """
        assert rules_of(lint(src, self.SOLVER)) == []

    def test_outside_scoped_packages_stays_quiet(self):
        # models/pod.py declares the fields, codec moves them on/off the
        # wire, and the gang package owns the semantics — all out of scope
        src = """
        def encode(self, p):
            return (p.gang_id, p.gang_size)
        """
        assert rules_of(lint(src, "karpenter_tpu/service/codec.py")) == []
        assert rules_of(lint(src, "karpenter_tpu/gang/__init__.py")) == []
        assert rules_of(lint(src, "karpenter_tpu/models/pod.py")) == []

    def test_unrelated_attribute_stays_quiet(self):
        src = """
        def seat(self, pod):
            return pod.name, pod.priority
        """
        assert rules_of(lint(src, self.SOLVER)) == []

    def test_suppression_with_reason(self):
        src = """
        def audit(self, pod):
            # ktlint: allow[KT025] diagnostics-only dump, ISSUE 20
            return pod.gang_id
        """
        assert rules_of(lint(src, self.SOLVER)) == []

    def test_package_is_clean(self):
        # the contract's point: admission/ and solver/ route every gang
        # decision through karpenter_tpu.gang — zero raw field reads
        from karpenter_tpu.analysis.rules import kt025

        active, _supp, n_files = analyze_package(rules=[kt025])
        assert n_files > 60
        assert active == [], "\n".join(f.format() for f in active)
