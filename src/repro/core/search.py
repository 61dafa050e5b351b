"""MOAR global search (paper §4, Algorithms 1-3).

Search-space = a tree of complete pipelines rooted at the user pipeline.
Selection walks the tree with UCT whose reward is the *marginal accuracy
contribution* delta_t (pareto.contribution), under progressive widening
W(n) = max(2, 1 + sqrt(n)). Rewriting delegates to the AgentPolicy with
progressive disclosure and the paper's pruning rules (cycle + no-op).
Parameter-sensitive directives evaluate k candidates and keep the most
accurate (all k count toward the evaluation budget B).

Error handling (§4.3.3): instantiation failures retry inside the policy
and then discard; transient execution failures discard without retry; both
decrement the selected node's visit counts so failures don't inflate them.
Identical pipelines reuse cached measurements.

Parallelism: the search is a deterministic plan/execute/commit round
engine. Each round (a) selects up to ``round_width`` leaves under
virtual-loss UCT — every selection bumps visit counts along its path
before the next selection runs, so concurrent selections diverge instead
of piling onto one node; (b) instantiates every candidate pipeline up
front, seeding the agent from a monotonic *attempt counter* (never the
stalling budget counter); (c) evaluates the whole candidate set through
one cross-pipeline dispatch session (``Executor.run_session``), which
merges sibling candidates' LLM requests into shared ``Backend.submit``
batches; and (d) commits results into the tree in canonical plan order.
The planned round is a function of search state only and the session is
bit-identical to sequential evaluation, so ``workers=N`` yields
bit-identical frontiers, ``dstats``, and budget accounting to
``workers=1`` — workers is pure execution parallelism.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass, field, replace as _dc_replace
from typing import Any, Dict, List, Optional, Tuple

from repro.analysis.analyzer import lint_errors
from repro.core import pareto
from repro.core.agent import (AgentContext, AgentPolicy, DirectiveStats,
                              ModelStats)
from repro.core.directives import Directive, Target, applicable
from repro.core.models_catalog import catalog, model_names
from repro.engine.executor import (CallCache, Executor, TransientLLMError,
                                   evaluation_cache_stats)
from repro.engine.operators import (PipelineConfig, clone_pipeline,
                                    pipeline_hash, validate_pipeline)
from repro.engine.workloads import Workload
from repro.pipeline.model import PipelineLike, as_config
from repro.pipeline.optimizers import (PlanPoint,
                                       SearchResult as UnifiedResult)


@dataclass(eq=False)  # identity equality: nodes form a tree (deep __eq__
class Node:           # would recurse through parent/children/pipelines)
    pipeline: PipelineConfig
    acc: float = 0.0
    cost: float = 0.0
    parent: Optional["Node"] = None
    children: List["Node"] = field(default_factory=list)
    last_action: str = "ROOT"
    last_kind: str = ""
    depth: int = 0
    visits: int = 1
    disabled: bool = False
    directive_usage: Dict[str, int] = field(default_factory=dict)
    eval_index: int = 0  # iteration at which this node was evaluated

    def descendants(self) -> List["Node"]:
        out = []
        stack = list(self.children)
        while stack:
            n = stack.pop()
            out.append(n)
            stack.extend(n.children)
        return out

    def path_actions(self) -> List[str]:
        acts, n = [], self
        while n is not None and n.last_action != "ROOT":
            acts.append(n.last_action)
            n = n.parent
        return list(reversed(acts))


def widening_cap(visits: int) -> int:
    """W(n) = max(2, 1 + sqrt(n)) (paper §4.2)."""
    return max(2, int(1 + math.sqrt(visits)))


# leaves selected per round (virtual-loss UCT fan-out). An algorithm
# constant, deliberately NOT derived from ``workers`` — see MOARSearch.
DEFAULT_ROUND_WIDTH = 4


@dataclass
class SearchResult:
    root: Node
    evaluated: List[Node]
    frontier: List[Node]
    budget_used: int
    errors: int
    wall_s: float
    history: List[Dict[str, Any]] = field(default_factory=list)
    cache_stats: Dict[str, Any] = field(default_factory=dict)
    # round-engine accounting: rounds run, configured width/workers, and
    # the executor's merged-dispatch counters
    parallel_stats: Dict[str, Any] = field(default_factory=dict)
    # candidates the static analyzer rejected before evaluation ($0)
    static_rejects: int = 0
    static_rejects_by_directive: Dict[str, int] = field(default_factory=dict)

    def best(self) -> Node:
        return max(self.evaluated, key=lambda n: n.acc)


@dataclass
class _PlannedCandidate:
    """One candidate pipeline of a planned rewrite, fixed at plan time."""

    pipeline: PipelineConfig
    hash: str
    free: bool  # tier-1 hit at plan time: costs no budget to commit


@dataclass
class _PlannedRewrite:
    """One (node, directive) rewrite planned for a round: all candidate
    pipelines are instantiated up front; evaluation happens in the
    round's shared dispatch session; commit runs in plan order."""

    node: Node
    directive: Directive
    candidates: List[_PlannedCandidate]
    attempt: int

    @property
    def budget_need(self) -> int:
        return sum(1 for c in self.candidates if not c.free)


class MOARSearch:
    name = "moar"  # Optimizer-protocol registry name (repro.pipeline)

    def __init__(
        self,
        workload: Workload,
        backend,
        *,
        budget: int = 40,
        seed: int = 0,
        models: Optional[List[str]] = None,
        max_models: int = 12,  # C_m (paper footnote 2)
        workers: int = 1,
        round_width: Optional[int] = None,
        fail_prob: float = 0.0,
        reward: str = "contribution",   # | "hypervolume" (ablation, §4.2)
        progressive_widening: bool = True,  # ablation: uncapped branching
        lint: bool = True,  # static-analyze candidates before evaluating
        lint_fields: Optional[List[str]] = None,  # known source fields
        call_cache: Optional[CallCache] = None,  # e.g. a persistent tier
    ):
        self.workload = workload
        self.backend = backend
        self.budget = budget
        self.seed = seed
        self.models = (models or model_names())[:max_models]
        # round_width is an *algorithm* knob: how many leaves each round
        # selects under virtual-loss UCT. workers is an *execution* knob:
        # how many of the round's candidate evaluations run concurrently
        # in the dispatch session. Keeping them independent is what makes
        # workers=N bit-identical to workers=1 — the planned rounds are a
        # function of search state only. (workers > round-candidate count
        # simply leaves the extra slots idle.)
        self.workers = max(1, workers)
        self.round_width = round_width if round_width else DEFAULT_ROUND_WIDTH
        # two-tier evaluation cache (paper §4.3.3 measurement reuse):
        # tier 1 — self.cache, keyed by pipeline hash (identical candidate
        # = free); tier 2 — the executor's content-addressed call cache
        # (candidates sharing a prefix with anything already evaluated
        # only re-execute the changed suffix). An injected call_cache —
        # e.g. repro.cache.PersistentCallCache — adds a third, durable
        # tier: optimize() clears only the in-memory tiers, so a second
        # search over the same store warm-starts from the recorded calls
        self.call_cache = call_cache if call_cache is not None \
            else CallCache()
        self.executor = Executor(backend, fail_prob=fail_prob, seed=seed,
                                 call_cache=self.call_cache)
        self.policy = AgentPolicy(seed=seed)
        self.model_stats = ModelStats()
        self.dstats = DirectiveStats()
        self.cache: Dict[str, Tuple[float, float]] = {}
        self.cache_hits = 0
        self.evaluated: List[Node] = []
        self.t = 0
        # monotonic attempt counter: seeds every rewrite attempt. The
        # budget counter t stalls on cache hits, so seeding from it made
        # consecutive guard-loop iterations re-propose the identical
        # rewrite; attempts is bumped per planned rewrite, hit or miss.
        self.attempts = 0
        self.rounds = 0
        self.errors = 0
        self.reward = reward
        self.progressive_widening = progressive_widening
        # static analysis gate (repro.analysis): error-diagnosed
        # candidates are rejected before evaluation at zero token cost.
        # Without lint_fields the analyzer runs open-world (only provable
        # errors fire), so enabling lint is bit-identical to disabling it
        # on all-valid candidate streams; passing the dataset's field
        # names tightens undefined-read detection.
        self.lint = lint
        self.lint_fields = list(lint_fields) if lint_fields else None
        self.static_rejects = 0
        self.static_rejects_by_directive: Dict[str, int] = {}

    # -- evaluation ------------------------------------------------------------

    def _evaluate(self, pipeline: PipelineConfig) -> Tuple[float, float, bool]:
        """Returns (acc, cost, cached). Raises TransientLLMError upward."""
        h = pipeline_hash(pipeline)
        if h in self.cache:
            self.cache_hits += 1
            acc, cost = self.cache[h]
            return acc, cost, True
        out, stats = self.executor.run(pipeline, self.workload.sample)
        acc = self.workload.score(out, self.workload.sample)
        self.cache[h] = (acc, stats.cost)
        return acc, stats.cost, False

    def _evaluate_many(self, pipelines: List[PipelineConfig]
                       ) -> List[Tuple[Optional[float], Optional[float],
                                       bool, Optional[Exception]]]:
        """Batched counterpart of :meth:`_evaluate`: one entry per input,
        ``(acc, cost, cached, error)``. Pipeline-hash (tier-1) hits are
        resolved at plan time; the rest evaluate through ONE dispatch
        session, whose results commit into the tier-1 cache in canonical
        order — exactly the order sequential ``_evaluate`` calls would
        have used, so workers only changes wall-clock. Duplicate hashes
        within the batch execute once; the second commits as a tier-1
        hit, same as it would have sequentially (if the first errored,
        the second evaluates on its own, also matching the replay)."""
        hashes = [pipeline_hash(p) for p in pipelines]
        job_of: List[Optional[int]] = []
        jobs: List[Tuple[PipelineConfig, Any]] = []
        planned = set(self.cache)
        for p, h in zip(pipelines, hashes):
            if h in planned:
                job_of.append(None)
            else:
                job_of.append(len(jobs))
                jobs.append((p, self.workload.sample))
                planned.add(h)
        session = self.executor.run_session(jobs, workers=self.workers) \
            if jobs else []
        out = []
        for p, h, ji in zip(pipelines, hashes, job_of):
            if h in self.cache:  # plan-time hit, or committed earlier here
                self.cache_hits += 1
                acc, cost = self.cache[h]
                out.append((acc, cost, True, None))
                continue
            if ji is None:
                # duplicate whose leader errored: evaluate sequentially,
                # exactly as the replayed _evaluate chain would
                try:
                    out.append(self._evaluate(p) + (None,))
                except TransientLLMError as e:
                    out.append((None, None, False, e))
                continue
            res = session[ji]
            if res.error is not None:
                out.append((None, None, False, res.error))
                continue
            acc = self.workload.score(res.docs, self.workload.sample)
            self.cache[h] = (acc, res.stats.cost)
            out.append((acc, res.stats.cost, False, None))
        return out

    def _commit_node(self, pipeline, parent, action, kind, acc, cost,
                     cached: bool) -> Node:
        node = Node(pipeline=pipeline, acc=acc, cost=cost, parent=parent,
                    last_action=action, last_kind=kind,
                    depth=(parent.depth + 1 if parent else 0),
                    eval_index=self.t)
        if parent is not None:
            parent.children.append(node)
        if not cached:
            self.t += 1
        self.evaluated.append(node)
        return node

    def _add_node(self, pipeline, parent, action, kind) -> Optional[Node]:
        try:
            acc, cost, cached = self._evaluate(pipeline)
        except TransientLLMError:
            self.errors += 1
            return None
        return self._commit_node(pipeline, parent, action, kind, acc, cost,
                                 cached)

    def cache_stats(self) -> Dict[str, Any]:
        """Hit accounting for both evaluation-cache tiers."""
        return evaluation_cache_stats(self.cache_hits, len(self.cache),
                                      self.call_cache)

    # -- initialization (paper §4.1) --------------------------------------------

    def _initialize(self) -> Node:
        p0 = clone_pipeline(self.workload.initial_pipeline)
        validate_pipeline(p0)
        root = None
        for _ in range(4):  # transient API failures: retry the root
            root = self._add_node(p0, None, "ROOT", "")
            if root is not None:
                break
        assert root is not None, "initial pipeline failed to evaluate"
        # model variants of P0 as children: plan the whole sweep up front
        # (clamped to the remaining budget BEFORE the first evaluation),
        # evaluate it as one batched session, commit in model order. The
        # sweep widens the root at once, so it takes no more models than
        # the root's widening admits when the budget is spent, with one
        # round's slack: the cheapest of the pool
        variants: List[Tuple[str, PipelineConfig]] = []
        budget_left = self.budget - self.t
        sweep = self.models
        if self.progressive_widening:
            cards = catalog()
            n = widening_cap(self.budget) + self.round_width - 1
            kept = set(sorted(sweep, key=lambda m: cards[m].price_in
                              + cards[m].price_out)[:n])
            sweep = [m for m in sweep if m in kept]
        for m in sweep:
            variant = clone_pipeline(p0)
            changed = False
            for op in variant["operators"]:
                if op.get("model"):
                    op["model"] = m
                    changed = True
            if not changed:
                continue
            if pipeline_hash(variant) not in self.cache:
                if budget_left <= 0:
                    break
                budget_left -= 1
            variants.append((m, variant))
        results = self._evaluate_many([v for _, v in variants])
        for (m, variant), (acc, cost, cached, err) in zip(variants, results):
            if err is not None:
                self.errors += 1
                continue
            node = self._commit_node(variant, root, f"model_sub({m})",
                                     "model", acc, cost, cached)
            self.model_stats.acc[m] = node.acc
            self.model_stats.cost[m] = node.cost
        # frontier members spawn one accuracy- and one cost-targeted
        # rewrite — planned as one round, evaluated in one session
        frontier = pareto.pareto_set([root] + root.children)
        planned: List[_PlannedRewrite] = []
        budget_left = self.budget - self.t
        for node in list(frontier):
            for objective in ("improve accuracy",
                              "reduce cost while preserving accuracy"):
                if budget_left <= 0:
                    break
                pr = self._plan_rewrite(node, budget_left,
                                        objective_override=objective)
                if pr is None:
                    continue
                planned.append(pr)
                budget_left -= pr.budget_need
        self._execute_and_commit(planned)
        # disable non-frontier model variants from future selection
        for child in root.children:
            if child not in frontier:
                child.disabled = True
        self._bump_visits(root)
        return root

    # -- selection (Algorithm 2) --------------------------------------------------

    def _delta(self, node: Node) -> float:
        if self.reward == "hypervolume":
            # ablation — classic hypervolume contribution: every frontier
            # point counts, including low-accuracy ones (the paper argues
            # this wastes budget in low-accuracy regions)
            ref = max((n.cost for n in self.evaluated), default=1.0) * 1.1
            with_p = pareto.hypervolume(self.evaluated, ref)
            without = pareto.hypervolume(
                [n for n in self.evaluated if n is not node], ref)
            return (with_p - without) / max(ref, 1e-9)
        return pareto.contribution(node, self.evaluated)

    def _utility(self, node: Node) -> float:
        d = self._delta(node) + sum(self._delta(x) for x in node.descendants())
        exploit = d / node.visits
        parent_visits = node.parent.visits if node.parent else node.visits
        explore = math.sqrt(2.0 * math.log(max(parent_visits, 2))
                            / node.visits)
        return exploit + explore

    def _select(self, root: Node) -> Node:
        node = root
        while True:
            kids = [c for c in node.children if not c.disabled]
            cap = widening_cap(node.visits) if self.progressive_widening \
                else 10 ** 9
            if len(node.children) < cap or not kids:
                break
            node = max(kids, key=self._utility)
        # visit increments along the path (Alg 2 lines 8-11)
        n = node
        while n is not None:
            n.visits += 1
            n = n.parent
        return node

    def _bump_visits(self, node: Node):
        node.visits = 1 + len(node.descendants())

    def _unbump(self, node: Node):
        """Failed attempt: roll the selection's visit increment back."""
        n = node
        while n is not None:
            n.visits = max(1, n.visits - 1)
            n = n.parent

    # -- pruning (paper §4.3.2) ----------------------------------------------------

    def _prune(self, node: Node,
               allowed: List[Tuple[Directive, List[Target]]]):
        has_split = any(op["type"] == "split"
                        for op in node.pipeline["operators"])
        out = []
        for d, targets in allowed:
            # cycle: chaining immediately followed by fusion reverses it
            if node.last_kind == "chaining" and d.kind == "fusion":
                continue
            # cycle: model substitution at a first-layer node only revisits
            # models the initialization already covered
            if d.name == "model_substitution" and node.depth <= 1:
                continue
            # no-op: chunking a pipeline that already chunks
            if d.name in ("doc_chunking",) and has_split:
                continue
            # no-op: consecutive compression/summarization
            if d.kind == "compression" and node.last_kind == "compression":
                continue
            out.append((d, targets))
        return out

    # -- rewriting & evaluation (Algorithm 3) -----------------------------------------

    def _objective_for(self, node: Node) -> str:
        ranked = sorted(self.evaluated, key=lambda n: -n.acc)
        rank = ranked.index(node) + 1 if node in ranked else len(ranked)
        if rank <= len(self.evaluated) / 2:
            return "reduce cost while preserving accuracy"
        return "improve accuracy"

    def _plan_rewrite(self, node: Node, budget_left: int,
                      objective_override: Optional[str] = None
                      ) -> Optional[_PlannedRewrite]:
        """Stage (b) of a round: choose a directive for ``node`` and
        instantiate ALL its candidate pipelines up front. The agent seed
        derives from the monotonic attempt counter — a cache hit leaves
        the budget counter t unchanged, so seeding from t re-proposed the
        identical rewrite forever. Candidates are clamped to
        ``budget_left`` BEFORE the first evaluation (tier-1 hits are
        free and don't count). Returns None (and rolls back the
        selection's visit bump) when nothing is plannable."""
        attempt = self.attempts
        self.attempts += 1
        objective = objective_override or self._objective_for(node)
        ctx = AgentContext(self.workload.sample, self.workload.tags,
                           seed=self.seed + 31 * attempt,
                           model_stats=self.model_stats,
                           objective=objective)
        allowed = self._prune(node, applicable(node.pipeline))
        choice = self.policy.choose_directive(
            node.pipeline, allowed, ctx, self.dstats,
            node.directive_usage, node.depth)
        if choice is None:
            self._unbump(node)
            return None
        directive, target = choice
        node.directive_usage[directive.name] = \
            node.directive_usage.get(directive.name, 0) + 1
        candidates: List[_PlannedCandidate] = []
        # lint-retry loop: when every instantiated candidate is rejected
        # by the static analyzer, re-seed the agent (salting PAST the
        # policy's internal per-exception attempt salts) and re-propose —
        # the reject feedback costs zero tokens. Round 0 uses ctx
        # unchanged, so on all-valid streams this is bit-identical to the
        # pre-lint single pass.
        lint_rounds = self.policy.max_retries if self.lint else 1
        for lint_round in range(lint_rounds):
            retry_ctx = ctx if lint_round == 0 else \
                ctx.with_attempt(lint_round * self.policy.max_retries)
            try:
                param_sets = self.policy.instantiate(
                    directive, node.pipeline, target, retry_ctx)
            except RuntimeError:
                self.errors += 1
                self._unbump(node)
                return None
            if not directive.param_sensitive:
                param_sets = param_sets[:1]
            need = 0
            rejected = 0
            for params in param_sets:
                try:
                    new_pipeline = self._transform_candidate(
                        directive.apply(node.pipeline, target, params),
                        directive, attempt)
                    validate_pipeline(new_pipeline)
                except Exception:  # noqa: BLE001 — bad rewrite, next params
                    self.errors += 1
                    continue
                if self.lint and lint_errors(
                        new_pipeline, source_fields=self.lint_fields):
                    self.static_rejects += 1
                    self.static_rejects_by_directive[directive.name] = \
                        self.static_rejects_by_directive.get(
                            directive.name, 0) + 1
                    rejected += 1
                    continue
                h = pipeline_hash(new_pipeline)
                free = h in self.cache
                if not free:
                    if need >= budget_left:
                        break
                    need += 1
                candidates.append(_PlannedCandidate(new_pipeline, h, free))
            if candidates or rejected == 0:
                break
        if not candidates:
            self._unbump(node)
            return None
        return _PlannedRewrite(node=node, directive=directive,
                               candidates=candidates, attempt=attempt)

    def _transform_candidate(self, pipeline: PipelineConfig,
                             directive: Directive,
                             attempt: int) -> PipelineConfig:
        """Seam between directive application and validation/lint; the
        default is identity. Fault-injection tests and the lint bench
        override it to corrupt a deterministic fraction of rewrites."""
        return pipeline

    def _execute_and_commit(self, planned: List[_PlannedRewrite]) -> None:
        """Stages (c)+(d) of a round: evaluate every planned candidate
        through one cross-pipeline dispatch session, then commit results
        into the tree in canonical plan order — node creation, budget
        accounting, best-candidate selection, and directive statistics
        all happen exactly as a sequential walk of the plan would."""
        if not planned:
            return
        flat = [c for pr in planned for c in pr.candidates]
        results = self._evaluate_many([c.pipeline for c in flat])
        i = 0
        for pr in planned:
            new_nodes: List[Node] = []
            for cand in pr.candidates:
                acc, cost, cached, err = results[i]
                i += 1
                if err is not None:
                    self.errors += 1
                    continue
                child = self._commit_node(cand.pipeline, pr.node,
                                          f"{pr.directive.name}",
                                          pr.directive.kind, acc, cost,
                                          cached)
                new_nodes.append(child)
            if not new_nodes:
                self._unbump(pr.node)
                continue
            best = max(new_nodes, key=lambda n: n.acc)
            # non-best candidates stay evaluated (count toward B,
            # contribute to the frontier) but are not extended further
            for c in new_nodes:
                if c is not best:
                    c.disabled = True
            self.dstats.update(pr.directive.name, best.acc - pr.node.acc,
                               best.cost - pr.node.cost)

    # -- main loop (Algorithm 1) ---------------------------------------------------------

    def run(self) -> SearchResult:
        t0 = time.time()
        root = self._initialize()
        history = []
        guard = 0
        while self.t < self.budget and guard < self.budget * 6:
            guard += 1
            # plan: select up to round_width leaves under virtual-loss
            # UCT (_select bumps visits along the path, so the next
            # selection sees the loss and diverges) and instantiate every
            # candidate, clamped to the remaining budget
            planned: List[_PlannedRewrite] = []
            budget_left = self.budget - self.t
            for _ in range(self.round_width):
                if budget_left <= 0:
                    break
                node = self._select(root)
                pr = self._plan_rewrite(node, budget_left)
                if pr is None:
                    continue
                planned.append(pr)
                budget_left -= pr.budget_need
            # execute + commit: one dispatch session, canonical order
            self._execute_and_commit(planned)
            if planned:
                self.rounds += 1
            front = pareto.pareto_set(self.evaluated)
            history.append({
                "t": self.t,
                "round": self.rounds,
                "planned": sum(len(pr.candidates) for pr in planned),
                "frontier_size": len(front),
                "best_acc": max(n.acc for n in self.evaluated),
            })
        frontier = pareto.pareto_set(self.evaluated)
        # the user-authored plan is always surfaced as a fallback option
        # (Fig 4 plots it alongside the frontier)
        if root not in frontier:
            frontier.append(root)
        # dedup identical (cost, acc) points for a readable frontier
        seen, dedup = set(), []
        for n in sorted(frontier, key=lambda n: (n.cost, -n.acc, n.eval_index)):
            key = (round(n.cost, 9), round(n.acc, 9))
            if key in seen:
                continue
            seen.add(key)
            dedup.append(n)
        frontier = dedup
        return SearchResult(
            root=root,
            evaluated=list(self.evaluated),
            frontier=frontier,
            budget_used=self.t,
            errors=self.errors,
            wall_s=time.time() - t0,
            history=history,
            cache_stats=self.cache_stats(),
            parallel_stats={
                "workers": self.workers,
                "round_width": self.round_width,
                "rounds": self.rounds,
                "attempts": self.attempts,
                **self.executor.dispatch_stats,
            },
            static_rejects=self.static_rejects,
            static_rejects_by_directive=dict(
                self.static_rejects_by_directive),
        )

    # -- unified Optimizer protocol (repro.pipeline) -----------------------------------

    def optimize(self, pipeline: Optional[PipelineLike] = None,
                 workload: Optional[Workload] = None,
                 budget: Optional[int] = None) -> UnifiedResult:
        """Shared ``Optimizer.optimize()`` entry point: run the MOAR
        search and report the optimizer-agnostic ``SearchResult``
        (PlanPoints carry the rewrite path / eval index in ``meta``; the
        native tree result rides in ``native``). Each call is a fresh
        search: evaluation list, budget use, caches, and agent statistics
        are reset (the measurement cache is keyed by pipeline hash only,
        so carrying it across workload overrides would report a previous
        workload's scores)."""
        if workload is not None:
            self.workload = workload
        if pipeline is not None:
            self.workload = _dc_replace(self.workload,
                                        initial_pipeline=as_config(pipeline))
        if budget is not None:
            self.budget = budget
        self.cache = {}
        self.cache_hits = 0
        self.call_cache.clear()
        self.evaluated = []
        self.t = 0
        self.attempts = 0
        self.rounds = 0
        self.errors = 0
        self.static_rejects = 0
        self.static_rejects_by_directive = {}
        self.model_stats = ModelStats()
        self.dstats = DirectiveStats()
        for k in self.executor.dispatch_stats:
            self.executor.dispatch_stats[k] = 0
        res = self.run()

        def point(n: Node) -> PlanPoint:
            return PlanPoint(n.pipeline, n.acc, n.cost, note=n.last_action,
                             meta={"path": n.path_actions(),
                                   "eval_index": n.eval_index,
                                   "depth": n.depth})

        return UnifiedResult(
            optimizer=self.name,
            evaluated=[point(n) for n in res.evaluated],
            frontier=[point(n) for n in res.frontier],
            budget_used=res.budget_used,
            wall_s=res.wall_s,
            errors=res.errors,
            native=res,
            cache_stats=res.cache_stats,
            parallel_stats=res.parallel_stats,
            static_rejects=res.static_rejects,
            static_rejects_by_directive=dict(
                res.static_rejects_by_directive),
        )

    # -- held-out evaluation ----------------------------------------------------------

    def evaluate_on_test(self, nodes: List[Node]) -> List[Dict[str, Any]]:
        out = []
        for n in nodes:
            docs, stats = self.executor.run(n.pipeline, self.workload.test)
            out.append({
                "pipeline": n.pipeline,
                "path": n.path_actions(),
                "sample_acc": n.acc,
                "test_acc": self.workload.score(docs, self.workload.test),
                "test_cost": stats.cost,
                "latency_s": stats.latency_s,
                "n_ops": len(n.pipeline["operators"]),
            })
        return out
