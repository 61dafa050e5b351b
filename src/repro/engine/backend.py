"""Execution backends for semantic operators.

SimBackend
----------
A deterministic, seeded generative model of LLM behaviour over synthetic
documents, calibrated to the phenomena the paper's optimizer exploits.
Documents carry hidden *facts* — (tag, value) pairs embedded as sentences
whose surface form either contains the tag's canonical keyword or a
paraphrase (keyword absent). The backend simulates an LLM reading the
document's *current text* (so upstream compression/chunking genuinely
gates what downstream operators can find):

- recall of a fact = model capability x task-complexity factor (number of
  task_tags the prompt asks for at once) x context-length factor (decays
  toward the model's MRCR-style long-context score; text beyond the
  context window is truncated) x per-(model,tag) seeded noise;
- paraphrased facts are only found by LLMs (scaled by capability); code
  ops (regex/keyword, codeops.py) match canonical keywords exactly —
  cheap, precise, bounded recall;
- prompt-engineering features (clarified / few-shot, set by directives)
  give bounded boosts that are larger for weaker models (paper §B.5.2);
- per-(model, domain) specialization jitter makes the best model
  workload-dependent (paper Table 6);
- costs follow the paper's cost model: tokens x per-token price of the
  model, prices derived from each arch's FLOP/byte counts (models_catalog).

Determinism: every stochastic decision hashes (seed, doc id, op fields,
model, tag) — identical pipelines on identical data give identical
results, which the executor's cache relies on (paper §4.3.3).

JaxBackend
----------
Operators execute real forward passes of reduced-config JAX models from
the pool (real tokenization, prefill/decode, token counting). Used by
integration tests and the serving example — it validates the substrate,
not extraction quality (models are untrained).

Both backends implement the batched Backend protocol v2
(``submit(list[OpRequest]) -> list[OpResult]``): SimBackend as a
vectorized per-request sweep (a pure function gains nothing from
batching but must answer the batched surface), JaxBackend by routing
generation chunks through the continuous-batching scheduler. The legacy
per-document ``run_*`` methods remain as the kind-specific
implementations and keep v1 compatibility via ``LegacyBackendAdapter``.
"""

from __future__ import annotations

import hashlib
import re
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from repro.core.models_catalog import ModelCard, catalog
from repro.data.documents import (Dataset, Document, doc_text,
                                  main_text_key, word_count)
from repro.engine.codeops import sentences
from repro.pipeline.protocols import OpRequest, OpResult, execute_request

WORDS_PER_TOKEN = 0.75


@dataclass
class Usage:
    in_tokens: int = 0
    out_tokens: int = 0
    calls: int = 0

    def add(self, other: "Usage"):
        self.in_tokens += other.in_tokens
        self.out_tokens += other.out_tokens
        self.calls += other.calls


def tokens_of(text: str) -> int:
    return int(word_count(text) / WORDS_PER_TOKEN) + 1


# hidden per-model text-task capability (the optimizer never sees these;
# it only observes measured accuracy/cost)
_CAPABILITY = {
    "grok-1-314b": 0.95,
    "gemma3-27b": 0.92,
    "granite-34b": 0.90,
    "granite-4.0-h-small": 0.89,  # assumed: a 32B-A9B hybrid MoE
    "gemma2-9b": 0.88,
    "zamba2-2.7b": 0.78,
    "llama3.2-1b": 0.74,
    "granite-moe-1b-a400m": 0.70,
    "internvl2-1b": 0.66,
    "mamba2-370m": 0.60,
    "whisper-medium": 0.50,
}


def _hash01(*parts) -> float:
    h = hashlib.blake2s("|".join(str(p) for p in parts).encode()).digest()
    return int.from_bytes(h[:8], "little") / 2**64


def default_equijoin(op: Dict[str, Any], doc: Document
                     ) -> Tuple[Optional[Dict], Usage]:
    """Semantic join of one document against ``op['right_docs']``: the
    shared implementation both backends (and the LegacyBackendAdapter
    fallback) use. Returns (``right_*``-prefixed fields of the best
    match, or None) plus the per-probe usage."""
    right = op.get("right_docs", [])
    lval = str(doc.get(op["left_field"], "")).lower()
    fld_r = op["right_field"]
    best = None
    for r in right:
        if str(r.get(fld_r, "")).lower() == lval:
            best = r
            break
    usage = Usage(in_tokens=40 * max(len(right), 1), out_tokens=4, calls=1)
    if best is None:
        return None, usage
    return {f"right_{k}": v for k, v in best.items()
            if not k.startswith("_")}, usage


class SimBackend:
    # Backend-protocol batching hint: the simulator is a pure function of
    # (seed, doc, op), so any chunking yields identical results — but
    # cross-pipeline dispatch sessions merge sibling candidates' request
    # streams, and a real batched endpoint amortizes per-call overhead
    # across the chunk. Advertise a real batch so merged (mixed-pipeline,
    # mixed-op) stages ride fewer ``submit`` round-trips.
    preferred_batch_size = 16
    # results depend only on (seed, domain, op, doc): the executor's
    # content-addressed call cache may memoize invocations
    deterministic = True
    # ...and submit holds no mutable state, so a dispatch session may
    # keep several chunks of a merged stage in flight at once
    concurrent_submit = True

    def __init__(self, seed: int = 0, domain: str = "generic",
                 cards: Optional[Dict[str, ModelCard]] = None):
        self.seed = seed
        self.domain = domain
        self.cards = cards or catalog()

    def fingerprint(self) -> Tuple[Any, ...]:
        # custom card sets change context windows and therefore results:
        # key them by content (prices + windows), not object identity
        from repro.data.documents import content_hash
        cards_fp = None if self.cards is catalog() else content_hash(
            sorted((name, str(card)) for name, card in self.cards.items()))
        return ("sim", self.seed, self.domain, cards_fp)

    # -- batched dispatch (Backend protocol v2) -------------------------------

    def submit(self, requests: List[OpRequest]) -> List[OpResult]:
        """Vectorized entry point: the simulator is a pure per-request
        function, so the batch executes as a straight sweep (via the
        shared kind -> ``run_*`` routing) — no cross-request state, any
        chunking yields identical results."""
        out = []
        for req in requests:
            value, usage = execute_request(self, req)
            out.append(OpResult(value=value, usage=usage))
        return out

    # -- internals ----------------------------------------------------------

    def _card(self, model: str) -> ModelCard:
        return self.cards[model]

    def _quality(self, model: str, op: Dict[str, Any]) -> float:
        base = _CAPABILITY[model]
        # per-(model, domain) specialization: +-0.06
        jitter = (_hash01(self.seed, "spec", model, self.domain) - 0.5) * 0.12
        q = base + jitter
        feats = op.get("prompt_features", {})
        weak = 1.0 - base
        boost = 0.0
        if feats.get("clarified"):
            boost += min(0.08, 0.03 + 0.10 * weak) * min(feats["clarified"], 2)
        if feats.get("few_shot"):
            boost += min(0.06, 0.02 + 0.08 * weak)
        if feats.get("gleaning"):
            # validator-feedback rounds (DocETL-V1 gleaning)
            boost += 0.04 * min(feats["gleaning"], 2)
        # prompt tricks interact SUB-additively: stacking clarify + few-shot
        # + gleaning on one operator saturates (real LLMs don't compound
        # prompt hacks linearly) — greedy single-op stacking plateaus, and
        # structural rewrites (what MOAR searches) stay the bigger lever
        q += min(boost, 0.055 + 0.07 * weak)
        return min(q, 0.99)

    def _complexity_factor(self, op: Dict[str, Any], n_words: int) -> float:
        """Task difficulty: how many task units the prompt asks for at
        once (task_tags), floored by the task's intrinsic breadth (e.g.
        biodex's 24k-label space -> task_breadth). Effective breadth
        scales with the visible context: the same question over a 300-word
        chunk is easier than over the full document — this is what makes
        the paper's data-decomposition rewrites pay off."""
        n = max(len(op.get("task_tags", [])), op.get("task_breadth", 1))
        scale = min(1.0, (max(n_words, 50) / 2000.0) ** 0.5)
        n_eff = 1.0 + (n - 1) * scale
        return 0.975 ** max(n_eff - 1.0, 0.0)

    def _context_factor(self, model: str, n_words: int) -> Tuple[float, int]:
        """Returns (quality multiplier, visible words)."""
        card = self._card(model)
        window_words = int(card.context_window * WORDS_PER_TOKEN)
        visible = min(n_words, window_words)
        frac = visible / max(window_words, 1)
        if frac <= 0.25:
            f = 1.0
        else:
            # linear decay from 1.0 at 25% toward long_context_score at 100%
            f = 1.0 - (frac - 0.25) / 0.75 * (1.0 - card.long_context_score)
        return f, visible

    def _present_facts(self, doc: Document) -> List[Dict[str, Any]]:
        """Facts whose evidence sentence survives in the current text."""
        text = doc_text(doc)
        out = []
        for f in doc.get("_facts", []):
            idx = text.find(f["value"])
            if idx >= 0:
                pos_words = word_count(text[:idx])
                out.append({**f, "pos_words": pos_words})
        return out

    def _usage(self, op, in_text_tokens: int, out_tokens: int) -> Usage:
        prompt_toks = tokens_of(op.get("prompt", "")) + 30
        feats = op.get("prompt_features", {})
        if feats.get("few_shot"):
            prompt_toks += 120 * min(feats["few_shot"], 4)
        mult = 1.0 + 0.6 * min(feats.get("gleaning", 0), 3)
        if op.get("lean_output"):
            out_tokens = max(4, int(out_tokens * 0.6))
        return Usage(in_tokens=int((prompt_toks + in_text_tokens) * mult),
                     out_tokens=int(out_tokens * mult),
                     calls=1 + min(feats.get("gleaning", 0), 3))

    def usage_cost(self, model: str, usage: Usage) -> float:
        card = self._card(model)
        return (usage.in_tokens * card.price_in
                + usage.out_tokens * card.price_out) / 1e6

    # -- semantic operator implementations -----------------------------------

    def run_map(self, op: Dict[str, Any], doc: Document) -> Tuple[Dict, Usage]:
        model = op["model"]
        if op.get("format_field"):
            # formatting/narrative map over pre-aggregated items (the LLM
            # half of a code_reduce split): cheap, high fidelity
            items = doc.get(op["format_field"]) or []
            q = self._quality(model, op)
            kept = [i for i in items
                    if _hash01(self.seed, "fmt", model, str(i)) < min(0.995, q + 0.15)]
            schema = op.get("output_schema", {})
            out_field = next(iter(schema), "formatted")
            usage = self._usage(op, 12 * max(len(items), 1),
                                10 * max(len(kept), 1))
            return {out_field: kept}, usage
        tags = op.get("task_tags", [])
        text = doc_text(doc)
        nw = word_count(text)
        q = self._quality(model, op)
        cf, visible = self._context_factor(model, nw)
        comp = self._complexity_factor(op, nw)
        present = self._present_facts(doc)

        found = []
        for f in present:
            if f["tag"] not in tags:
                continue
            if f["pos_words"] > visible:   # truncated out of the window
                continue
            p = q * comp * cf
            if f.get("paraphrased"):
                p *= 0.55 + 0.45 * q       # paraphrase: capability-gated
            r = _hash01(self.seed, "map", doc.get("id"), model, f["tag"],
                        f["value"], op.get("prompt_features", {}),
                        len(tags) // 8)
            if r < p:
                found.append(f)
        # hallucinations: rate grows with task breadth, shrinks with quality
        halls = []
        fp_rate = 0.015 * (1.0 - q) * (1 + len(tags) / 16)
        for tag in tags:
            r = _hash01(self.seed, "fp", doc.get("id"), model, tag)
            if r < fp_rate:
                halls.append({"tag": tag, "value": f"spurious_{tag[:12]}"})

        schema = op.get("output_schema", {})
        out_field = next(iter(schema), "extractions")
        include_evidence = op.get("include_evidence", True)
        items = []
        for f in found:
            item = {"tag": f["tag"], "value": f["value"]}
            if include_evidence:
                item["evidence"] = f"...{f['value']}..."
            items.append(item)
        items += [{"tag": h["tag"], "value": h["value"]} for h in halls]
        out_tokens = 8 + 18 * len(items)
        fields = {out_field: items}
        flag_spec = op.get("emit_filter_flag")
        if flag_spec:
            # fused map+filter: the map also evaluates the filter predicate
            # (a joint task — slightly harder than a dedicated filter call)
            ftag = flag_spec.get("tag", "")
            if ftag:
                truth = any(f["tag"] == ftag for f in present)
            else:
                truth = bool(doc.get(flag_spec.get("truth_field", "_keep"),
                                     True))
            r = _hash01(self.seed, "fusedflt", doc.get("id"), model, ftag,
                        flag_spec.get("truth_field", ""))
            correct = r < q * cf * 0.98
            fields[flag_spec["field"]] = truth if correct else not truth
            out_tokens += 4
        return fields, self._usage(
            op, int(min(nw, visible) / WORDS_PER_TOKEN), out_tokens)

    def run_classify(self, op: Dict[str, Any], doc: Document,
                     classes: List[str], truth_field: str
                     ) -> Tuple[str, Usage]:
        """map specialization: single-label classification."""
        model = op["model"]
        text = doc_text(doc)
        q = self._quality(model, op)
        cf, visible = self._context_factor(model, word_count(text))
        comp = self._complexity_factor(
            {"task_breadth": max(len(classes) // 4, 1)}, word_count(text))
        truth = doc.get(truth_field, classes[0])
        r = _hash01(self.seed, "cls", doc.get("id"), model, truth_field,
                    op.get("prompt_features", {}))
        if r < q * cf * comp:
            label = truth
        else:
            idx = int(_hash01(self.seed, "clswrong", doc.get("id"), model)
                      * len(classes))
            label = classes[min(idx, len(classes) - 1)]
        return label, self._usage(op, int(visible / WORDS_PER_TOKEN), 12)

    def run_filter(self, op: Dict[str, Any], doc: Document
                   ) -> Tuple[bool, Usage]:
        model = op["model"]
        tag = op.get("filter_tag", "")
        text = doc_text(doc)
        q = self._quality(model, op)
        cf, visible = self._context_factor(model, word_count(text))
        if tag:
            truth = any(f["tag"] == tag for f in self._present_facts(doc))
        else:
            truth = bool(doc.get(op.get("filter_truth_field", "_keep"), True))
        r = _hash01(self.seed, "flt", doc.get("id"), model, tag,
                    op.get("prompt_features", {}))
        correct = r < q * cf
        keep = truth if correct else not truth
        if op.get("bias_recall") and truth:
            # recall-biased pre-filter (cascade stage): never drops a true
            # positive; precision errors remain
            keep = True
        return keep, \
            self._usage(op, int(visible / WORDS_PER_TOKEN), 4)

    def run_reduce(self, op: Dict[str, Any], docs: Dataset
                   ) -> Tuple[Dict, Usage]:
        """Aggregates either pre-extracted fields (cheap, accurate) or raw
        text (the whole group's text becomes the context — expensive and
        context-limited, the BlackVault failure mode)."""
        model = op["model"]
        q = self._quality(model, op)
        agg_field = op.get("aggregate_field")
        usage = Usage()
        items: List[Any] = []
        if agg_field and any(agg_field in d for d in docs):
            # combine pre-extracted lists; upstream evidence improves dedup
            has_evidence = any(
                isinstance(v, list) and v and isinstance(v[0], dict)
                and "evidence" in v[0]
                for v in (d.get(agg_field) for d in docs) if v)
            dedup_q = min(0.98, q + (0.10 if has_evidence else 0.0))
            # combining is easier than extraction but not free: each unique
            # item survives the merge with quality-dependent probability —
            # a weak merge model quietly drops findings, so the chunk-merge
            # model choice interacts with upstream rewrites (paper §1)
            keep_q = min(0.995, q + 0.12)
            seen = set()
            for d in docs:
                vals = d.get(agg_field) or []
                vals = vals if isinstance(vals, list) else [vals]
                for v in vals:
                    key = str(v.get("value", v) if isinstance(v, dict) else v)
                    r = _hash01(self.seed, "dedup", model, key)
                    if key in seen and r < dedup_q:
                        continue  # correctly deduplicated
                    if key not in seen:
                        seen.add(key)
                        if _hash01(self.seed, "mergekeep", model, key) < keep_q:
                            items.append(v)
            in_toks = sum(tokens_of(str(d.get(agg_field, ""))) for d in docs)
            usage.add(self._usage(op, in_toks, 12 * max(len(items), 1)))
        else:
            # re-analyze raw text of the whole group in one call
            joined = " ".join(doc_text(d) for d in docs)
            tags = op.get("task_tags", [])
            nw_joined = word_count(joined)
            cf, visible = self._context_factor(model, nw_joined)
            comp = self._complexity_factor(op, nw_joined)
            budget_words = 0
            for d in docs:
                present = self._present_facts(d)
                t = doc_text(d)
                offset = budget_words
                budget_words += word_count(t)
                for f in present:
                    if not tags or f["tag"] in tags:
                        if offset + f["pos_words"] > visible:
                            continue
                        p = q * comp * cf
                        if f.get("paraphrased"):
                            p *= 0.55 + 0.45 * q
                        r = _hash01(self.seed, "redraw", model, f["value"])
                        if r < p:
                            items.append({"tag": f["tag"], "value": f["value"]})
            usage.add(self._usage(op, int(visible / WORDS_PER_TOKEN),
                                  12 * max(len(items), 1)))
        schema = op.get("output_schema", {})
        out_field = next(iter(schema), "aggregated")
        return {out_field: items}, usage

    def run_summarize(self, op: Dict[str, Any], doc: Document
                      ) -> Tuple[Dict, Usage]:
        """LLM document summarization (projection synthesis): output is a
        REWRITE — recalled facts are re-stated in canonical form (an LLM
        normalizes paraphrases), noise is dropped. Downstream code ops can
        therefore match facts that were paraphrased in the original."""
        model = op["model"]
        text = doc_text(doc)
        q = self._quality(model, op)
        cf, visible = self._context_factor(model, word_count(text))
        kept = []
        for f in self._present_facts(doc):
            if f["pos_words"] > visible:
                continue
            p = min(0.98, q * cf + 0.03)
            if f.get("paraphrased"):
                p *= 0.65 + 0.35 * q
            if _hash01(self.seed, "summ", doc.get("id"), model,
                       f["value"]) < p:
                kept.append(f)
        key = main_text_key(doc)
        lines = [f"summary of the source document ({len(kept)} findings)."]
        for f in kept:
            lines.append(
                f"the record notes a [{f['tag']}] matter involving "
                f"{f['value']}.")
        summary = " ".join(lines)
        usage = self._usage(op, int(visible / WORDS_PER_TOKEN),
                            tokens_of(summary))
        return {key: summary}, usage

    def run_extract(self, op: Dict[str, Any], doc: Document
                    ) -> Tuple[Dict, Usage]:
        """LLM-based document compression: returns line ranges -> text
        subset. Finds fact sentences incl. paraphrases (capability-gated);
        output tokens are just the ranges (cheap)."""
        model = op["model"]
        tags = op.get("task_tags", [])
        text = doc_text(doc)
        q = self._quality(model, op)
        cf, visible = self._context_factor(model, word_count(text))
        kept_values = []
        for f in self._present_facts(doc):
            if tags and f["tag"] not in tags:
                continue
            if f["pos_words"] > visible:
                continue
            p = min(0.98, (q * cf) + 0.05)  # extraction is easier than QA
            if f.get("paraphrased"):
                p *= 0.6 + 0.4 * q
            if _hash01(self.seed, "ext", doc.get("id"), model,
                       f["value"]) < p:
                kept_values.append(f["value"])
        sents = sentences(text)
        kept = [s for s in sents if any(v in s for v in kept_values)]
        # keep ~10% neutral context lines
        kept += [s for i, s in enumerate(sents)
                 if _hash01(self.seed, "extn", doc.get("id"), i) < 0.10]
        # explicit text_key override wins; default to the main text field
        key = op.get("text_key") or main_text_key(doc)
        usage = self._usage(op, int(visible / WORDS_PER_TOKEN), 30)
        return {key: " ".join(dict.fromkeys(kept))}, usage

    def run_equijoin(self, op: Dict[str, Any], doc: Document
                     ) -> Tuple[Optional[Dict], Usage]:
        """Semantic join probe: exact-match against op['right_docs']."""
        return default_equijoin(op, doc)

    def run_resolve(self, op: Dict[str, Any], docs: Dataset
                    ) -> Tuple[Dataset, Usage]:
        """Canonicalize near-duplicate values of a field across docs."""
        model = op["model"]
        fld = op.get("resolve_field", "")
        q = self._quality(model, op)
        usage = Usage()
        canon: Dict[str, str] = {}
        out = []
        for d in docs:
            v = str(d.get(fld, ""))
            base = re.sub(r"[^a-z0-9]", "", v.lower())
            r = _hash01(self.seed, "res", model, base)
            key = base if r < q else v
            canon.setdefault(key, v)
            nd = dict(d)
            nd[fld] = canon[key]
            out.append(nd)
            usage.add(Usage(in_tokens=tokens_of(v) + 20, out_tokens=8, calls=1))
        return out, usage


class JaxBackend:
    """Operators run real model forward passes from the pool.

    ``reduced=True`` (default) serves each architecture's small smoke
    config; ``reduced=False`` serves the published config at full width.
    Params are initialized from ``seed`` on JAX's default device.

    ``submit`` batches generation: requests are grouped by model and run
    through the fixed-slot continuous batcher (``serving/scheduler.py``),
    so prefill/decode of a chunk genuinely amortizes — one jitted decode
    step serves every active slot. Encoder-decoder and VLM architectures
    need extra prefill inputs the scheduler doesn't thread, so they fall
    back to per-request decoding.
    """

    # Backend-protocol batching hint: real decoding amortizes prefill
    # across requests. Chunks may exceed the decode slot count — the
    # continuous batcher queues the overflow and admits as slots retire,
    # so merged mixed-pipeline stages from a dispatch session still
    # drain in one ``run_until_drained`` sweep per model.
    preferred_batch_size = 8
    # fixed decode-batch width of the continuous batcher (default; the
    # constructor's ``decode_slots`` overrides per instance — serving
    # hosts size it to their traffic via ``--slots``)
    DECODE_SLOTS = 4
    # NOT memoizable: the fixed-slot batcher pads every slot to the max
    # active length, so a request's decoded tokens depend on which other
    # requests share its chunk — caching would freeze one batch
    # composition's answer and make search order-dependent
    deterministic = False

    # prompt truncation: the serving path tokenizes at most this many ids
    MAX_PROMPT_TOKENS = 96

    def __init__(self, seed: int = 0, max_new_tokens: int = 8,
                 decode_slots: Optional[int] = None,
                 strict_compile: bool = False,
                 reduced: bool = True):
        import jax
        from repro.configs import get_config
        from repro.models import api
        self._api = api
        self._get_config = get_config
        self._jax = jax
        self.seed = seed
        self.max_new_tokens = max_new_tokens
        self.reduced = reduced
        # compile-path static-analysis gate (repro.analysis.compiled):
        # every model is audited once at load. False (default) runs the
        # fast jaxpr tier and surfaces findings as warnings; True also
        # compiles the decode step and raises on any error diagnostic.
        self.strict_compile = strict_compile
        if decode_slots is not None:
            self.DECODE_SLOTS = max(1, int(decode_slots))
        self._params = {}
        self._batchers: Dict[str, Any] = {}
        self.cards = catalog()

    def fingerprint(self) -> Tuple[Any, ...]:
        return ("jax", self.seed, self.max_new_tokens, self.DECODE_SLOTS,
                self.reduced)

    def close(self) -> None:
        """Backend lifecycle hook (``backend_close``): drop the model
        params and per-model batchers so device buffers are reclaimable
        once a serving host shuts down."""
        self._batchers.clear()
        self._params.clear()

    def _model(self, name: str):
        if name not in self._params:
            self._audit_compile(name)
            cfg = self._get_config(name, reduced=self.reduced)
            params = self._api.init_params(
                self._jax.random.PRNGKey(self.seed), cfg)
            self._params[name] = (cfg, params)
        return self._params[name]

    # process-wide audit memo: the lint is a pure function of the arch's
    # (frozen) config, so one report serves every backend instance
    _audit_cache: Dict[Tuple[str, bool, bool], Any] = {}

    def _audit_compile(self, name: str) -> None:
        """Construction-time compile-path lint gate: warn by default,
        raise under ``strict_compile`` (errors always fatal there; the
        jaxpr tier alone is milliseconds, so the default path stays
        cheap — the HLO tier only runs when strict)."""
        import warnings

        from repro.analysis.compiled import audit_model
        key = (name, self.strict_compile, self.reduced)
        report = self._audit_cache.get(key)
        if report is None:
            report = audit_model(name, compile=self.strict_compile,
                                 reduced=self.reduced)
            self._audit_cache[key] = report
        if self.strict_compile:
            report.raise_for_errors()
        for d in report.diagnostics:
            warnings.warn(f"compile-lint: {d.format()}", stacklevel=3)

    # -- batched dispatch (Backend protocol v2) -------------------------------

    def submit(self, requests: List[OpRequest]) -> List[OpResult]:
        # a profiler span on the trace's clock (see serving/scheduler.py)
        with self._jax.profiler.TraceAnnotation("backend.submit",
                                                requests=len(requests)):
            results: List[Optional[OpResult]] = [None] * len(requests)
            by_model: Dict[str, List[int]] = {}
            for i, req in enumerate(requests):
                if req.kind == "resolve":
                    results[i] = OpResult(value=list(req.docs),
                                          usage=Usage())
                elif req.kind == "equijoin":
                    value, usage = default_equijoin(req.op, req.doc)
                    results[i] = OpResult(value=value, usage=usage)
                else:
                    by_model.setdefault(req.op["model"], []).append(i)
            for model, idxs in by_model.items():
                prompts = [self._prompt_for(requests[i]) for i in idxs]
                for i, (toks, usage) in zip(
                        idxs, self._generate_batch(model, prompts)):
                    results[i] = OpResult(
                        value=self._value_for(requests[i], toks),
                        usage=usage)
            return results

    def _prompt_for(self, req: OpRequest) -> str:
        op = req.op
        if req.kind in ("map", "summarize", "filter"):
            return f"{op.get('prompt', '')}\n{doc_text(req.doc)[:2000]}"
        if req.kind == "extract":
            return doc_text(req.doc)[:2000]
        if req.kind == "classify":
            return doc_text(req.doc)[:1000]
        if req.kind == "reduce":
            joined = " ".join(doc_text(d)[:400] for d in req.docs[:8])
            return f"{op.get('prompt', '')}\n{joined}"
        raise TypeError(f"JaxBackend cannot execute request kind "
                        f"{req.kind!r}")

    def _value_for(self, req: OpRequest, toks: List[int]) -> Any:
        op = req.op
        if req.kind in ("map", "summarize"):
            out_field = next(iter(op.get("output_schema", {})), "output")
            return {out_field: [{"tag": "gen",
                                 "value": " ".join(map(str, toks))}]}
        if req.kind == "filter":
            return bool(toks[0] % 2)
        if req.kind == "extract":
            key = op.get("text_key") or main_text_key(req.doc)
            words = doc_text(req.doc).split()
            return {key: " ".join(words[:len(words) // 2])}
        if req.kind == "classify":
            classes = req.extra["classes"]
            return classes[toks[0] % len(classes)]
        out_field = next(iter(op.get("output_schema", {})), "aggregated")
        return {out_field: [{"tag": "gen", "value": str(t)} for t in toks]}

    def _batcher(self, model: str):
        """Persistent per-model continuous batcher: the jitted decode
        step compiles once and is reused across submit calls
        (``run_until_drained`` drains per call, so batches don't mix)."""
        b = self._batchers.get(model)
        if b is None:
            from repro.serving.scheduler import ContinuousBatcher
            cfg, params = self._model(model)
            b = ContinuousBatcher(
                params, cfg, num_slots=self.DECODE_SLOTS,
                max_len=self.MAX_PROMPT_TOKENS + self.max_new_tokens + 8,
                eos_id=-1)  # match generate(): no early EOS stop
            self._batchers[model] = b
        return b

    def _generate_batch(self, model: str, texts: List[str]
                        ) -> List[Tuple[List[int], Usage]]:
        import numpy as np
        from repro.data.tokenizer import HashWordTokenizer
        cfg, params = self._model(model)
        if cfg.is_encoder_decoder or cfg.family == "vlm":
            # extra prefill inputs (frames / patch embeds) aren't threaded
            # through the scheduler — decode these per request
            return [self._generate(model, t) for t in texts]
        tok = HashWordTokenizer(cfg.vocab_size)
        batcher = self._batcher(model)
        ids_list = [tok.encode(t)[:self.MAX_PROMPT_TOKENS] for t in texts]
        uids = [batcher.submit(np.asarray(ids, np.int32),
                               max_new_tokens=self.max_new_tokens)
                for ids in ids_list]
        finished = {r.uid: r for r in batcher.run_until_drained()}
        out = []
        for uid, ids in zip(uids, ids_list):
            usage = Usage(in_tokens=len(ids),
                          out_tokens=self.max_new_tokens, calls=1)
            out.append((list(finished[uid].generated), usage))
        return out

    def _generate(self, model: str, text: str) -> Tuple[List[int], Usage]:
        import numpy as np
        from repro.data.tokenizer import HashWordTokenizer
        from repro.serving.decode import generate
        cfg, params = self._model(model)
        tok = HashWordTokenizer(cfg.vocab_size)
        ids = tok.encode(text)[:self.MAX_PROMPT_TOKENS]
        prompt = np.asarray(ids, dtype=np.int32)[None, :]
        extra = {}
        if cfg.is_encoder_decoder:
            rng = np.random.default_rng(self.seed)
            extra["frames"] = rng.standard_normal(
                (1, cfg.encoder_seq_len, cfg.d_model)).astype("float32") * 0.1
        if cfg.family == "vlm":
            rng = np.random.default_rng(self.seed)
            vd = cfg.vit_dim or cfg.d_model
            extra["patch_embeds"] = rng.standard_normal(
                (1, cfg.num_patches, vd)).astype("float32") * 0.1
        out = generate(params, cfg, self._jax.numpy.asarray(prompt),
                       self.max_new_tokens, extra_inputs=extra or None)
        usage = Usage(in_tokens=len(ids), out_tokens=self.max_new_tokens,
                      calls=1)
        return list(out[0]), usage

    def usage_cost(self, model: str, usage: Usage) -> float:
        card = self.cards[model]
        return (usage.in_tokens * card.price_in
                + usage.out_tokens * card.price_out) / 1e6

    def _run_one(self, req: OpRequest) -> Tuple[Any, Usage]:
        """v1 per-request path: same prompt construction and output
        shaping as the batched path, minus the scheduler."""
        toks, usage = self._generate(req.op["model"], self._prompt_for(req))
        return self._value_for(req, toks), usage

    def run_map(self, op, doc):
        return self._run_one(OpRequest("map", op, doc=doc))

    def run_filter(self, op, doc):
        return self._run_one(OpRequest("filter", op, doc=doc))

    def run_reduce(self, op, docs):
        return self._run_one(OpRequest("reduce", op, docs=list(docs)))

    def run_extract(self, op, doc):
        return self._run_one(OpRequest("extract", op, doc=doc))

    def run_classify(self, op, doc, classes, truth_field):
        return self._run_one(OpRequest(
            "classify", op, doc=doc,
            extra={"classes": classes, "truth_field": truth_field}))

    def run_equijoin(self, op, doc):
        return default_equijoin(op, doc)

    def run_resolve(self, op, docs):
        usage = Usage()
        return list(docs), usage
