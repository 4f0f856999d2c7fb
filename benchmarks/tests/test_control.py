"""The comparison that decides ``correct`` fails its control: the plain
reference put in the program's place with one guarantee of the configuration
broken.  Sizes a test run can hold; the readings at the cells' own sizes are
in PERF.md (``control.py`` prints them)."""

import json
import os

import pytest

import gen
import plainref

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)

#: (configuration, guarantee broken, the number that has to fail)
CONTROLS = [
    ("c2-50k-3az", "spread", "violations"),
    ("c2-50k-3az", "price", "cost_ratio_max"),
    ("c3-10k-antiaffinity", "anti", "violations"),
    ("c3-10k-antiaffinity", "taints", "violations"),
    ("c3-10k-antiaffinity", "price", "cost_ratio_max"),
]


def setting(config, scale, seed):
    cfg = gen.load_config(config)
    rows = gen.load_catalog(cfg["catalog"])
    cluster = gen.burst_pool(cfg, 2, seed % 24, scale)[0]
    return cfg, rows, cluster


def verdict(cfg, rows, cluster, answer, unanswered=0):
    return plainref.compare(
        [(cluster.groups, answer)], gen.provisioners_plain(cfg),
        rows["types"], rows["zones"],
        float(cfg["guarantees"]["cost_ceiling"]), unanswered)


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11, 977])
@pytest.mark.parametrize("config,rule,number", CONTROLS)
def test_control_is_not_correct(config, rule, number, seed):
    cfg, rows, cluster = setting(config, 0.1, seed)
    provs = gen.provisioners_plain(cfg)
    sound = plainref.ffd(cluster.groups, provs, rows["types"], rows["zones"])
    v = verdict(cfg, rows, cluster, sound)
    assert v["correct"] and v["cost_ratio"] == pytest.approx(1.0)
    broken = plainref.ffd(cluster.groups, provs, rows["types"],
                          rows["zones"], break_rule=rule)
    v = verdict(cfg, rows, cluster, broken)
    assert not v["correct"]
    value, limit = v["numbers"][number]
    assert value > limit, v["numbers"]


def test_an_unanswered_request_is_not_correct():
    cfg, rows, cluster = setting("c2-50k-3az", 0.02, 5)
    sound = plainref.ffd(cluster.groups, gen.provisioners_plain(cfg),
                         rows["types"], rows["zones"])
    assert not verdict(cfg, rows, cluster, sound, unanswered=1)["correct"]


def test_every_seed_sends_the_same_requests_salted_and_reordered():
    cfg = gen.load_config("c2-50k-3az")
    n = gen.load_traffic("burst")["pool"]
    pool = gen.burst_pool(cfg, n)
    assert [c.key for c in pool] == [c.key for c in gen.burst_pool(cfg, n)]
    for c in pool:
        assert c.n_pods == 50_000 and len(c.groups) == 20
        assert sorted(c.key[1]) == sorted(pool[0].key[1])
    assert len({c.key for c in pool}) == n
    warm = gen.burst_pool(cfg, 3, n)
    assert not {c.key for c in warm} & {c.key for c in pool}
    a, b = gen.salted(pool[0], 1), gen.salted(pool[0], 2 ** 31 + 5)
    assert [g["name"] for g in a.groups] != [g["name"] for g in b.groups]
    shape = lambda c: sorted((g["cpu"], g["memory"], len(g["pods"]))  # noqa
                             for g in c.groups)
    assert shape(a) == shape(b) == shape(pool[0])
    again = gen.salted(pool[0], 1)
    assert [g["pods"] for g in a.groups] == [g["pods"] for g in again.groups]


def test_reconcile_deck_keeps_the_cluster_standing():
    cfg = gen.load_config("c2-50k-3az")
    traffic = gen.load_traffic("reconcile")
    steps = gen.Steps(cfg, traffic, 9)
    names = {nm for g in steps.settle().groups for nm in g["pods"]}
    assert len(names) == len(steps.live) == 50_000
    for _ in range(600):
        s = steps.next()
        names.difference_update(s["removed"])
        assert not names.intersection(s["added"])
        names.update(s["added"])
        assert 50_000 <= len(steps.live) <= 50_000 + 54 * 63
    settled = steps.settle()
    assert names == {nm for g in settled.groups for nm in g["pods"]}
    assert steps.kinds["scale_down"] * 9 == steps.kinds["scale_up"]
    assert settled.n_pods == len(names)


def unsalted(steps, n):
    """``n`` steps with the seed taken out: deployments by their place in
    the configuration, pods by their ordinal."""
    salt = steps.cluster.groups[0]["name"][-6:]
    assert all(g["name"].endswith(salt) for g in steps.cluster.groups)
    out = []
    for _ in range(n):
        s = steps.next()
        out.append((s["kind"],
                    steps.cluster.groups[s["group"]]["deployment"]
                    if s["added"] else -1,
                    [nm.replace(salt, "") for nm in s["added"]],
                    [nm.replace(salt, "") for nm in s["removed"]]))
    return salt, out


@pytest.mark.parametrize("config", ["c2-50k-3az", "c3-10k-antiaffinity",
                                    "longtail-15k"])
def test_every_seed_gets_the_same_steps_under_other_names(config):
    """The step stream is the configuration's: two seeds differ in the
    names and in the order of the deployments inside the cluster, and in
    nothing else; three decks of it."""
    cfg = gen.load_config(config)
    traffic = gen.load_traffic("reconcile")
    one, other = (gen.Steps(cfg, traffic, seed, 0.2)
                  for seed in (7, 2 ** 31 + 4099))
    shape = lambda st: sorted(  # noqa: E731
        (g["deployment"], g["cpu"], g["memory"], len(g["pods"]))
        for g in st.cluster.groups)
    assert shape(one) == shape(other)
    assert ([g["deployment"] for g in one.cluster.groups]
            != [g["deployment"] for g in other.cluster.groups])
    (salt_a, a), (salt_b, b) = unsalted(one, 180), unsalted(other, 180)
    assert salt_a != salt_b and a == b
    assert not one.deck and one.decks_dealt == 3
    # and the clusters at the boundary are one problem
    size = lambda st: sorted(  # noqa: E731
        (g["deployment"], len(g["pods"])) for g in st.settle().groups)
    assert size(one) == size(other)


def test_two_constructions_give_equal_steps_and_the_warm_stream_its_own():
    cfg = gen.load_config("c2-50k-3az")
    traffic = gen.load_traffic("reconcile")
    a, b = (gen.Steps(cfg, traffic, 11, 0.05) for _ in range(2))
    steps_a = [a.next() for _ in range(120)]
    assert steps_a == [b.next() for _ in range(120)]
    assert a.settle().groups == b.settle().groups
    warm = gen.Steps(cfg, traffic, 11, 0.05, stream="warm")
    assert warm.cluster.groups == gen.Steps(cfg, traffic, 11,
                                            0.05).cluster.groups
    for spec in traffic["warm_steps"]:
        warm.next((spec["kind"], int(spec.get("n", 0))))
    # forced steps take no card, and the warm pass ends where it began:
    # as many pods, other ones; the window's ledger never saw it
    assert not warm.deck and warm.decks_dealt == 0
    fresh = gen.Steps(cfg, traffic, 11, 0.05)
    assert len(warm.live) == len(fresh.live)
    assert sorted(warm.live) != sorted(fresh.live)


# ---- the reconcile kind itself, the plain reference in the session's place


class Clock:
    def __init__(self) -> None:
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


class PlainSession:
    """``run.Reconcile``'s session and RPC replaced by the plain reference:
    every step re-packs the ledger (a second a step on the test's clock).
    ``broken`` maps a step's number in the window to a guarantee broken in
    that step's pack; ``left_out`` is the step whose first added pod gets no
    node until the step after."""

    def __init__(self, kind, broken=None, left_out=None) -> None:
        cfg = gen.load_config("c2-50k-3az")
        rows = gen.load_catalog(cfg["catalog"])
        self.args = (gen.provisioners_plain(cfg), rows["types"],
                     rows["zones"])
        self.verdict_args = self.args + (
            float(cfg["guarantees"]["cost_ceiling"]), 0)
        self.kind, self.clock = kind, Clock()
        self.broken, self.left_out = broken or {}, left_out
        self.n = 0
        kind.sess, kind._step = self, self.step

    def step(self, step: dict):
        self.n += 1
        self.clock.t += 1.0
        self.ans = plainref.ffd(self.kind.steps.settle().groups, *self.args,
                                break_rule=self.broken.get(self.n))
        if self.n == self.left_out:
            name = step["added"][0]
            del self.ans.assignments[name]
            self.ans.infeasible[name] = "left out"
        return 1.0, "full"

    def result(self):
        from types import SimpleNamespace as NS

        return NS(assignments=self.ans.assignments,
                  infeasible=self.ans.infeasible,
                  nodes=[NS(name=n[0], instance_type=n[1], provisioner=n[2],
                            zone=n[3], capacity_type=n[4], price=n[5],
                            pods=[NS(name=nm) for nm in n[6]
                                  if nm in self.ans.assignments])
                         for n in self.ans.nodes])

    def window(self, passes: int, seconds: float = None) -> dict:
        """``(verdict, the boundaries line)`` of a window that the clock
        closes at the ``passes``-th boundary (or after ``seconds``, run on
        as an untraced run is)."""
        import run

        deck = len(self.kind.steps.deck_def)
        out = run.drive(
            self.kind, seconds or (passes - 1) * deck + 1.0, self.clock,
            passes=self.kind.cost_passes)
        assert out["cuts"] == [deck * (k + 1) for k in range(passes)]
        v = plainref.compare(
            self.kind.cases(), *self.verdict_args,
            unplaced_on_the_way=self.kind.unplaced_on_the_way)
        self.kind.full_resends = 1
        self.kind.report(out["walls"])
        lines = [json.loads(e) for e in run.EARLIER]
        return v, [e for e in lines if e["info"] == "boundaries"][-1]


def reconcile_kind(seed=2 ** 31 + 7):
    import run

    cfg = gen.load_config("c2-50k-3az")
    return run.Reconcile(gen.ProgramInputs(cfg), cfg,
                         gen.load_traffic("reconcile"), seed, 0.008)


@pytest.mark.parametrize("boundaries,cost_passes,what", [
    (1, 2, ["ceiling"]),
    (2, 2, ["metric", "metric"]),
    (3, 2, ["metric", "metric", "ceiling"]),
    (6, 2, ["metric", "metric", None, None, None, "ceiling"]),
    (4, 1, ["metric", None, None, "ceiling"]),
    (0, 2, []),
])
def test_which_boundaries_are_priced(boundaries, cost_passes, what):
    """The first ``cost_passes`` make the metric (none where the window held
    fewer), and the ceiling is held on them and on the last."""
    assert gen.boundary_costs(boundaries, cost_passes) == what
    assert {plainref.METRIC, plainref.CEILING, None} >= set(what)


@pytest.mark.parametrize("dear_in_passes,cost_ratio_reads", [
    ((3, 4), 1.0),       # drift: sound while the metric is read
    ((4,), 1.0),         # only the view the window closes on
    ((2, 3, 4), None),   # dear while the metric is read, too
    ((3,), "correct"),   # dear between the priced boundaries, mended after
])
def test_cost_is_read_at_the_first_boundaries_and_held_at_the_last_too(
        dear_in_passes, cost_ratio_reads):
    """Four passes; in some of them the session opens the dearest nodes.
    Every boundary is a case.  ``cost_ratio`` reads the first ``cost_passes``
    of them whatever comes later; the ceiling is held there AND on the view
    the window closes on, so a session whose $ drift with the passes is not
    correct; the reference is packed for those and for no other."""
    kind = reconcile_kind()
    deck, n = len(kind.steps.deck_def), kind.cost_passes
    assert n == 2
    dear = {k: "price" for p in dear_in_passes
            for k in range((p - 1) * deck + 1, p * deck + 1)}
    v, line = PlainSession(kind, broken=dear).window(4)
    assert v["compared"] == 4 == len(line["passes"]) == len(v["per_case"])
    assert ["ffd" in c for c in v["per_case"]] == [True, True, False, True]
    assert [p["cost_compared_for"] for p in line["passes"]] == [
        "metric", "metric", None, "ceiling"]
    assert line["in_cost_ratio"] == 2
    cost = [c["cost"] for c in v["per_case"]]
    if cost_ratio_reads == "correct":
        # the one thing the fixed boundaries do not see, by design: its $
        # are in the run's output all the same
        assert v["correct"] and v["cost_ratio"] == pytest.approx(1.0)
        assert cost[2] > 1.5 * cost[1]
    else:
        assert not v["correct"]
        assert v["numbers"]["cost_ratio_max"][0] > 1.5
        if cost_ratio_reads:
            assert v["cost_ratio"] == pytest.approx(cost_ratio_reads)
            assert cost[3] > 1.5 * cost[1]
        else:
            assert v["cost_ratio"] > 1.2
    assert all(c["unplaced"] == c["violations"] == 0 for c in v["per_case"])
    assert [p["pass"] for p in line["passes"]] == [1, 2, 3, 4]


def test_an_untraced_window_runs_on_to_the_cost_passes_th_boundary():
    """The clock would close it at the first boundary; ``cost_ratio`` is
    never read off fewer boundaries than the traffic file says.  A window
    that may close there (a traced run's) holds the ceiling and reports no
    ``cost_ratio``."""
    kind = reconcile_kind()
    v, line = PlainSession(kind).window(2, seconds=3.0)
    assert v["correct"] and v["cost_ratio"] == pytest.approx(1.0)
    assert line["in_cost_ratio"] == 2

    import run

    kind = reconcile_kind()
    sess = PlainSession(kind, broken={
        k + 1: "price" for k in range(len(kind.steps.deck_def))})
    out = run.drive(kind, 3.0, sess.clock)
    assert len(out["cuts"]) == 1
    v = plainref.compare(kind.cases(), *sess.verdict_args)
    assert v["cost_ratio"] is None and not v["correct"]
    assert v["numbers"]["cost_ratio_max"][0] > 1.5


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_the_reconcile_control_is_not_correct_late_or_throughout(seed):
    """``control.py``'s verdicts at a size a test can hold: the sound
    reference reads correct with ``cost_ratio`` 1; every guarantee broken
    reads not correct, and so does each broken at the last boundary alone,
    where ``cost_ratio`` itself still reads 1."""
    import control

    cell = {"config": "c2-50k-3az", "traffic": "reconcile"}
    got = dict(control.verdicts(cell, seed, 0.01))
    assert set(got) == {None, "price", "spread", "price@last", "spread@last"}
    assert got[None]["correct"] and got[None]["compared"] == 4
    for rule, v in got.items():
        if rule is None:
            continue
        assert not v["correct"], rule
        number = "violations" if rule.startswith("spread") \
            else "cost_ratio_max"
        value, limit = v["numbers"][number]
        assert value > limit, (rule, v["numbers"])
        if rule.endswith("@last"):
            assert [c["violations"] > 0 for c in v["per_case"]] == [
                False, False, False, rule == "spread@last"]
    assert got["price@last"]["cost_ratio"] == pytest.approx(1.0)
    assert got["price"]["cost_ratio"] > 1.2


def test_a_view_broken_at_a_boundary_that_is_not_the_last_is_not_correct():
    """The view at the first boundary ignores the zone spread; the next step
    mends it, and the window closes two passes later on a sound view."""
    kind = reconcile_kind()
    deck = len(kind.steps.deck_def)
    v, line = PlainSession(kind, broken={deck: "spread"}).window(3)
    assert not v["correct"] and v["numbers"]["violations"][0] > 0
    assert [c["violations"] > 0 for c in v["per_case"]] == [
        True, False, False]


def test_a_pod_left_out_by_one_step_and_seated_by_the_next_is_unplaced():
    """No boundary sees it; the count after every step does, and names the
    pass and the step."""
    kind = reconcile_kind()
    deck = len(kind.steps.deck_def)
    probe = gen.Steps(gen.load_config("c2-50k-3az"),
                      gen.load_traffic("reconcile"), 0, 0.008)
    kinds = [probe.next()["kind"] for _ in range(2 * deck)]
    at = next(k for k in range(deck + 3, 2 * deck - 1)
              if kinds[k - 1] == "scale_up")  # a step of the second pass
    v, line = PlainSession(kind, left_out=at).window(3)
    assert not v["correct"] and v["numbers"]["unplaced"] == [1, 0]
    assert v["numbers"]["violations"][0] == 0
    assert all(c["unplaced"] == 0 for c in v["per_case"])
    assert [p["unplaced_on_the_way"] for p in line["passes"]] == [0, 1, 0]
    (found,) = line["left_out_at"]
    assert (found["pass"], found["step"], found["kind"], found["mode"],
            found["why"]) == (2, at - deck, "scale_up", "full", "left out")
    assert line["unplaced_on_the_way"] == 1


def test_a_pod_left_out_by_the_step_that_ends_a_pass_is_named_too():
    """The boundary's validator counts it (not the count on the way, or it
    would count twice); where it was left out is reported all the same."""
    kind = reconcile_kind()
    deck = len(kind.steps.deck_def)
    probe = gen.Steps(gen.load_config("c2-50k-3az"),
                      gen.load_traffic("reconcile"), 0, 0.008)
    kinds = [probe.next()["kind"] for _ in range(6 * deck)]
    p = next(p for p in range(1, 7) if kinds[p * deck - 1] == "scale_up")
    v, line = PlainSession(kind, left_out=p * deck).window(max(p, 2))
    assert v["numbers"]["unplaced"] == [1, 0]
    assert [c["unplaced"] for c in v["per_case"]][p - 1] == 1
    assert line["unplaced_on_the_way"] == 0
    (found,) = line["left_out_at"]
    assert (found["pass"], found["step"]) == (p, deck)


def test_files_are_found_by_name_and_agree_with_benchmark_json(bench):
    for c in bench["configs"]:
        with open(os.path.join(os.path.dirname(BENCH), c["file"])) as f:
            doc = json.load(f)
        assert doc["name"] == c["name"] and doc["reduced"] == c["reduced"]
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(
            BENCH, "traffic", f"{w['traffic']}.json"))
    for m in bench["per_layer"]:
        with open(os.path.join(BENCH, "metrics", f"{m['name']}.json")) as f:
            spec = json.load(f)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert os.path.exists(os.path.join(
            BENCH, "readers", f"{spec['reader']}.py"))
