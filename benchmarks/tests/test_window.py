"""The window, ``run.drive`` + ``run.whole_passes``, on a clock the test
owns: no sidecar, no sleep.  What the command calls is what is called here;
``run_cell`` only hands the result on (``test_rehearsal.py``).  ``solve_ms``
and ``pods_per_s`` are the whole window's; the median pass and the stalled
passes stand beside them in the run's output."""

import random

import pytest

import gen
import run
from karpenter_tpu.solver.types import SolveResult


class Clock:
    """The requests below advance it; ``drive`` only reads it."""

    def __init__(self) -> None:
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


class Pool:
    """A burst-like kind: a pass is ``costs`` once, pass ``p`` stretched by
    ``stretch[p]`` (1.0 past its end); requests in ``fail_at`` raise after
    their wall has passed."""

    def __init__(self, clock, costs, stretch=(), pods=100, fail_at=()):
        self.clock, self.costs, self.stretch = clock, costs, list(stretch)
        self.pods, self.fail_at, self.sent = pods, set(fail_at), 0

    def request(self) -> int:
        i, self.sent = self.sent, self.sent + 1
        p, k = divmod(i, len(self.costs))
        self.clock.t += self.costs[k] * (
            self.stretch[p] if p < len(self.stretch) else 1.0)
        if i in self.fail_at:
            raise RuntimeError("refused")
        return self.pods

    def whole(self) -> bool:
        return self.sent % len(self.costs) == 0


COSTS = [1.0, 0.5, 2.0, 0.5]  # a pass of 4 s, by position as on the chip


def window(limit, **kw):
    clock = Clock()
    out = run.drive(Pool(clock, COSTS, **kw), limit, clock)
    return out, run.whole_passes(out)


def equal_passes():
    out, est = window(10.0)  # closes at the first boundary at or after 10 s
    assert est["passes"] == 3 and out["cuts"] == [4, 8, 12]
    assert est["pass_s"] == [4.0, 4.0, 4.0] and est["stalled_passes"] == 0
    # the window's wall over its requests, its pods over its wall — and the
    # median pass reads the same
    assert est["solve_ms"] == pytest.approx(out["window_s"] / 12 * 1000.0)
    assert est["median_solve_ms"] == pytest.approx(est["solve_ms"])
    assert est["pods_per_s"] == pytest.approx(
        out["pods_offered"] / out["window_s"])


def one_pass_stretched():
    _, calm = window(21.0)
    out, est = window(21.0, stretch=[1.0, 3.0])
    # 4 + 12 + 4 + 4 = 24 s, where the calm window fits 6 passes: the metric
    # is over all the time, so the stall is in it; the median pass stands
    # where it stood, and the run's output counts the stalled pass
    assert calm["passes"] == 6 and out["window_s"] == pytest.approx(24.0)
    assert est["pass_s"] == [4.0, 12.0, 4.0, 4.0]
    assert calm["solve_ms"] == pytest.approx(1000.0)
    assert est["solve_ms"] == pytest.approx(1500.0)
    assert est["pods_per_s"] == pytest.approx(calm["pods_per_s"] / 1.5)
    assert est["median_solve_ms"] == calm["median_solve_ms"] == 1000.0
    assert est["stalled_passes"] == 1 and calm["stalled_passes"] == 0


def every_pass_slower():
    _, base = window(20.0)
    _, slow = window(20.0, stretch=[1.1] * 9)
    assert slow["solve_ms"] == pytest.approx(base["solve_ms"] * 1.1)
    assert slow["median_solve_ms"] == pytest.approx(
        base["median_solve_ms"] * 1.1)
    assert slow["pods_per_s"] == pytest.approx(base["pods_per_s"] / 1.1)
    assert slow["stalled_passes"] == 0
    # a faster program is timed on more passes of the same requests
    assert slow["passes"] == 5 and base["passes"] == 5
    _, fast = window(20.0, stretch=[0.7] * 9)
    assert fast["passes"] == 8


def even_count():
    _, est = window(16.0, stretch=[1.0, 1.2, 1.1, 1.3])
    assert est["passes"] == 4
    # the middle two, halved: 4.4 and 4.8 s; the mean is 18.4 s over 16
    assert est["median_solve_ms"] == pytest.approx(1150.0)
    assert est["solve_ms"] == pytest.approx(1150.0)


def odd_count():
    _, est = window(19.0, stretch=[1.0, 1.2, 1.1, 1.3])
    assert est["passes"] == 5  # the fifth at 1.0: 4.0 4.8 4.4 5.2 4.0
    assert est["median_solve_ms"] == pytest.approx(1100.0)
    assert est["solve_ms"] == pytest.approx(22.4 / 20 * 1000.0)
    assert est["stalled_passes"] == 0  # 5.2 s is under 1.25 x 4.4


def single_pass():
    """A traced window of a long cell is one pass: its own median."""
    out, est = window(3.0, stretch=[1.05])
    assert est["passes"] == 1 and out["cuts"] == [4]
    assert est["solve_ms"] == pytest.approx(1050.0)
    assert est["median_solve_ms"] == pytest.approx(1050.0)
    assert est["stalled_passes"] == 0


def a_failed_request():
    out, est = window(10.0, fail_at=[5])
    assert out["failed"] == 1 and len(out["walls"]) == 12
    # its wall stays in its pass, its pods are not offered
    assert est["pass_s"] == [4.0, 4.0, 4.0]
    assert out["pods_offered"] == 1100
    assert est["solve_ms"] == est["median_solve_ms"] == 1000.0
    assert est["pods_per_s"] == pytest.approx(1100 / 12.0)


def the_deck_boundary():
    """The ``reconcile`` kind's pass is one whole deck: the kind itself over
    the traffic file's deck, its RPC replaced by the clock."""
    cfg = gen.load_config("c2-50k-3az")
    traffic = gen.load_traffic("reconcile")
    deck = sum(int(e.get("copies", 1)) for e in traffic["deck"])
    kind = run.Reconcile(gen.ProgramInputs(cfg), cfg, traffic, 2 ** 31 + 7,
                         0.008)
    clock = Clock()

    def step(step):  # a step costs by what it adds; the deck is a multiset
        clock.t += 0.05 + 0.001 * len(step["added"])
        return 0.0, "scan"

    kind._step = step

    class NoSession:  # what ``whole`` reads after every step: an empty view
        @staticmethod
        def result():
            return SolveResult(nodes=[], assignments={}, infeasible={})

    kind.sess = NoSession
    # a deck takes 3.567 s: the window runs on to the end of the second
    out = run.drive(kind, 5.0, clock)
    est = run.whole_passes(out)
    assert out["cuts"] == [deck, 2 * deck] and len(out["walls"]) == 2 * deck
    assert kind.steps.decks_dealt == 2 and not kind.steps.deck
    assert len(kind.cases()) == 2  # a case a pass boundary
    up = sum(int(e["n"]) * int(e["copies"]) for e in traffic["deck"]
             if e["kind"] == "scale_up")
    assert out["pods_offered"] == 2 * up
    # equal decks in another order: equal passes
    assert est["pass_s"][0] == pytest.approx(est["pass_s"][1])
    assert est["solve_ms"] == pytest.approx(est["median_solve_ms"])
    assert est["pods_per_s"] == pytest.approx(up / est["pass_s"][0])


CASES = [equal_passes, one_pass_stretched, every_pass_slower, even_count,
         odd_count, single_pass, a_failed_request, the_deck_boundary]


@pytest.mark.parametrize("case", CASES, ids=[c.__name__ for c in CASES])
def test_the_window_reads_whole_passes(case):
    case()


def test_a_stall_shows_in_the_metric_and_is_named_in_the_output():
    """The issue's own example: one 3 s stall inside one request of a 45 s
    window of c2.burst's shape (a pass 4.3 s) is 6 % of ``solve_ms`` — and
    the run says which kind of run it was."""
    rng = random.Random(5)
    costs = [1.2, 0.9, 1.3, 0.9]

    def one(stall_at):
        clock = Clock()
        kind = Pool(clock, costs)
        inner = kind.request

        def request():
            pods = inner()
            clock.t += rng.uniform(0.0, 0.004)  # the host's jitter
            if kind.sent - 1 == stall_at:
                clock.t += 3.0
            return pods

        kind.request = request
        return run.whole_passes(run.drive(kind, 45.0, clock))

    calm, stalled = one(None), one(17)
    assert stalled["solve_ms"] / calm["solve_ms"] > 1.05
    assert stalled["median_solve_ms"] / calm["median_solve_ms"] == (
        pytest.approx(1.0, abs=0.005))
    assert (calm["stalled_passes"], stalled["stalled_passes"]) == (0, 1)
