"""MOAR search invariants + error handling + determinism."""

import pytest

from repro.core import pareto
from repro.core.search import MOARSearch, widening_cap
from repro.engine.backend import SimBackend
from repro.engine.workloads import WORKLOADS


@pytest.fixture(scope="module")
def cuad_result():
    w = WORKLOADS["cuad"]()
    s = MOARSearch(w, SimBackend(seed=0, domain=w.domain), budget=40, seed=0)
    return w, s.run()


def test_budget_respected(cuad_result):
    _, res = cuad_result
    assert res.budget_used <= 40


def test_frontier_is_pareto_of_evaluated(cuad_result):
    _, res = cuad_result
    front = pareto.pareto_set(res.evaluated)
    front_keys = {(round(n.cost, 9), round(n.acc, 9)) for n in front}
    for n in res.frontier:
        if n.last_action == "ROOT":
            continue  # the user plan is always surfaced as a fallback
        assert (round(n.cost, 9), round(n.acc, 9)) in front_keys


def test_tree_structure_consistent(cuad_result):
    _, res = cuad_result
    seen = set()
    stack = [res.root]
    while stack:
        n = stack.pop()
        assert id(n) not in seen, "tree has a cycle"
        seen.add(id(n))
        for c in n.children:
            assert c.parent is n
            assert c.depth == n.depth + 1
            stack.append(c)
    # every evaluated node is in the tree
    for n in res.evaluated:
        assert id(n) in seen


def test_best_accuracy_improves_over_initial(cuad_result):
    _, res = cuad_result
    assert res.best().acc > res.root.acc + 0.1


def test_history_monotone(cuad_result):
    _, res = cuad_result
    best = [h["best_acc"] for h in res.history]
    assert all(b2 >= b1 - 1e-12 for b1, b2 in zip(best, best[1:]))


def test_visits_bounded_by_tree_size(cuad_result):
    _, res = cuad_result
    n_total = len(res.root.descendants()) + 1
    assert res.root.visits <= n_total * 3  # selection bumps are bounded


def test_progressive_widening_respected(cuad_result):
    """No node exceeds its widening cap by more than the parallel slack."""
    _, res = cuad_result
    stack = [res.root]
    while stack:
        n = stack.pop()
        if n.children:
            # candidates of one rewrite (param-sensitive k) share one edge
            # budget decision; allow that slack
            assert len(n.children) <= widening_cap(n.visits) + 3
        stack.extend(n.children)


def test_deterministic_same_seed():
    w = WORKLOADS["medec"]()
    r1 = MOARSearch(w, SimBackend(seed=3, domain=w.domain), budget=20,
                    seed=3).run()
    r2 = MOARSearch(w, SimBackend(seed=3, domain=w.domain), budget=20,
                    seed=3).run()
    assert [(n.acc, n.cost) for n in r1.evaluated] == \
        [(n.acc, n.cost) for n in r2.evaluated]


def test_error_handling_transient_failures():
    """With injected API failures the search completes and discards."""
    w = WORKLOADS["medec"]()
    s = MOARSearch(w, SimBackend(seed=5, domain=w.domain), budget=25,
                   seed=5, fail_prob=0.02)
    res = s.run()
    assert res.budget_used <= 25
    assert len(res.evaluated) >= 1
    # failures recorded, search survived
    assert res.errors >= 0


def test_parallel_workers_structure():
    """workers=3: the round engine never overshoots B (the old racy
    path could), and the parallel run matches sequential exactly (the
    full equivalence suite lives in test_search_parallel.py)."""
    w = WORKLOADS["medec"]()
    res = MOARSearch(w, SimBackend(seed=2, domain=w.domain), budget=24,
                     seed=2, workers=3).run()
    assert res.budget_used <= 24  # no parallel overshoot
    assert res.best().acc >= res.root.acc
    seq = MOARSearch(w, SimBackend(seed=2, domain=w.domain), budget=24,
                     seed=2, workers=1).run()
    assert [(n.acc, n.cost) for n in res.evaluated] == \
        [(n.acc, n.cost) for n in seq.evaluated]


def test_objective_split_by_rank(cuad_result):
    """Both objectives must be exercised: frontier spans a cost range."""
    _, res = cuad_result
    costs = [n.cost for n in res.frontier]
    assert max(costs) > min(costs) * 1.5 or len(costs) <= 2


def test_initialization_disables_non_frontier_model_variants(cuad_result):
    _, res = cuad_result
    variants = [c for c in res.root.children
                if c.last_action.startswith("model_sub(")]
    assert variants, "init must create model variants"
    front = pareto.pareto_set([res.root] + variants)
    for v in variants:
        if v not in front:
            assert v.disabled


@pytest.mark.parametrize("budget", [12, 40])
def test_initial_sweep_takes_the_cheapest_models_the_root_admits(budget):
    """The initialization's model variants widen the root at once: no more
    of them than the root's cap at the end of the budget plus one round's
    slack, and those of the cheapest models."""
    from repro.core.models_catalog import catalog
    from repro.core.search import DEFAULT_ROUND_WIDTH

    w = WORKLOADS["cuad"]()
    res = MOARSearch(w, SimBackend(seed=0, domain=w.domain), budget=budget,
                     seed=0).run()
    swept = [c.last_action[len("model_sub("):-1] for c in res.root.children
             if c.last_action.startswith("model_sub(")]
    cap = widening_cap(budget) + DEFAULT_ROUND_WIDTH - 1
    assert 0 < len(swept) <= cap
    cards = catalog()
    price = lambda m: cards[m].price_in + cards[m].price_out
    cheapest = sorted(cards, key=price)[:cap]
    assert set(swept) <= set(cheapest)
