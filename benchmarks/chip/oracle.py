"""How ``correct`` is decided: the served tokens against the plain
float32 reference, and the served outputs against the served tokens.

After the window has closed and the program's state is freed, a sample
of the requests the window served, drawn from the seed, is run through
the family's reference: each prompt (rebuilt by the benchmark from the
document) followed by the tokens the program served, teacher-forced in
one pass. At every served position the reference's best logit minus its
logit for the served token is that token's gap: 0 where the program chose
what the reference chooses, and small where rounding tipped a near tie.
The widest gap over the sample is compared with the cell's limit.

The control (``control=True``) runs the same sample through the
reference in fp8 arithmetic and reads, at each position, the float32
reference's gap for the token the fp8 pass ranks first. ``judge`` then
takes that reading in the served program's place, beside the run's
exact checks, and has to find the control not correct.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from benchmarks.chip import traffic as T


def sample(ids: Sequence[str], k: int, seed: int) -> List[str]:
    """``k`` of the served requests, drawn from the seed. Every request of
    a cell has the same prompt and output length, so any is a longest."""
    ids = sorted(ids)
    k = min(k, len(ids))
    picked = T.rng(seed, "sample").choice(len(ids), size=k, replace=False)
    return [ids[i] for i in sorted(picked)]


def gaps(fam, sizes: Dict[str, Any], seed: int,
         items: Sequence[Tuple[List[int], List[int]]], block: int,
         control: bool = False) -> Dict[str, Any]:
    """Per item ``(prompt ids, served tokens)``, the widest gap of a served
    token; with ``control``, also that of the fp8 pass's first choice.
    Weights are drawn from the seed here, rows go ``block`` at a time."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip.families import _mamba2 as M

    prompt_len = len(items[0][0])
    n_new = len(items[0][1])
    for prompt, served in items:
        if len(prompt) != prompt_len or len(served) != n_new:
            raise ValueError("every compared request must have the same "
                             "prompt and output length")
    start = prompt_len - 1
    f32, fp8 = M.Arith("f32"), M.Arith("fp8")

    def served_gap(w, toks, served):
        with jax.default_matmul_precision("highest"):
            lg = fam.logits(w, toks, sizes, f32, start)
            best = lg.max(-1)
            got = jnp.take_along_axis(lg, served[..., None], -1)[..., 0]
            out = {"served": (best - got).max(-1)}
            if control:
                low = fam.logits(w, toks, sizes, fp8, start).argmax(-1)
                alt = jnp.take_along_axis(lg, low[..., None], -1)[..., 0]
                out["control"] = (best - alt).max(-1)
            return out

    weights = jax.jit(lambda key: fam.init_weights(key, sizes))(
        jax.random.PRNGKey(seed))
    step = jax.jit(served_gap)
    per: Dict[str, List[float]] = {"served": [], "control": []}
    for lo in range(0, len(items), block):
        chunk = list(items[lo:lo + block])
        pad = block - len(chunk)
        chunk += [chunk[-1]] * pad   # one shape for every block
        toks = np.asarray([p + s[:-1] for p, s in chunk], np.int32)
        served = np.asarray([s for _, s in chunk], np.int32)
        out = jax.device_get(step(weights, toks, served))
        for key, vals in out.items():
            per[key].extend(float(v) for v in vals[:block - pad])
    del weights
    result = {"max_logit_gap": max(per["served"]),
              "per_request": per["served"]}
    if control:
        result["control_max_logit_gap"] = max(per["control"])
        result["control_per_request"] = per["control"]
    return result


def output_mismatches(op: Dict[str, Any], doc: Dict[str, Any],
                      docs_out: Any, tokens: List[int]) -> List[str]:
    """Where the user-visible output disagrees with the served tokens:
    a map writes them, space-joined, to its output field; a filter keeps
    the document when the first token is odd."""
    kind = op["type"]
    if kind == "map":
        field = next(iter(op["output_schema"]))
        try:
            value = docs_out[0][field][0]["value"]
        except (TypeError, KeyError, IndexError):
            return [f"{doc['id']}: no {field!r} output"]
        if value != " ".join(map(str, tokens)):
            return [f"{doc['id']}: output {value[:40]!r}... is not the "
                    f"served tokens"]
        return []
    if kind == "filter":
        kept = bool(docs_out)
        if kept != bool(tokens[0] % 2):
            return [f"{doc['id']}: kept={kept} but first token "
                    f"{tokens[0]}"]
        return []
    raise ValueError(f"no output check for operator type {kind!r}")


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Every compared number at or under its limit."""
    checks = {name: {"value": numbers[name], "limit": limits[name]}
              for name in limits}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
