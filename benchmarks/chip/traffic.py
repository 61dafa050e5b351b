"""Traffic: documents, arrival schedules and prompts, all from ``--seed``.

One generator reads every traffic file (``traffic/<mix>.json``). What a
seed changes is the order of a fixed set of sizes and the words of each
document, never the amount of work: every seed draws the same word
counts, shuffled, and the same arrival schedule.

- Documents are built in the manner of the program's synthetic workloads
  (``engine/workloads.py``): noise sentences of 8-17 words from a small
  administrative vocabulary with tagged fact sentences interleaved. Word
  counts follow a Pareto law truncated to ``[words_min, words_max]``,
  taken at evenly spaced quantiles.
- ``closed`` traffic keeps ``backlog`` documents outstanding: a
  completion releases the next document.
- ``poisson`` traffic is open-loop: arrivals at ``rate_per_s`` with
  exponential gaps, taken at evenly spaced quantiles of the exponential
  law (the arithmetic of ``benchmarks/serve_bench.py``'s
  ``poisson_arrivals``, with the gaps fixed and their order drawn). The
  order is drawn from the traffic's name, not the seed: a 95th percentile
  of some 80 requests follows the bursts the order makes, so a seed that
  reordered them changed the work (on one TPU v5e, spreads of 27-36% over
  6 seeds where two runs of one seed mostly agreed). Each request is
  timed from when it was due.

Prompts are rebuilt here, independently of the program: the operator's
prompt, a newline and the first ``PROMPT_CHARS`` characters of the
document, hashed word by word into the model's vocabulary after a BOS id,
and cut to ``MAX_PROMPT_TOKENS`` ids. These mirror the served program's
``JaxBackend`` (its prompt construction and ``HashWordTokenizer``).
"""

from __future__ import annotations

import hashlib
import math
import re
from typing import Any, Dict, List

import numpy as np

PROMPT_CHARS = 2000
MAX_PROMPT_TOKENS = 96
BOS_ID = 1
N_SPECIAL = 3

NOISE_WORDS = ("routine administrative filing reference section pursuant "
               "thereto standard provision general matter context detail "
               "record entry note update summary report item status").split()
TAGS = [f"clause_{i:02d}" for i in range(41)]

_WORD_RE = re.compile(r"\S+|\n")


def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent stream per purpose; any whole-number seed."""
    digest = hashlib.blake2s(f"{seed}|{stream}".encode()).digest()
    return np.random.default_rng(int.from_bytes(digest[:16], "little"))


def word_counts(t: Dict[str, Any], n: int, seed: int) -> List[int]:
    """``n`` document lengths: truncated-Pareto quantiles, shuffled."""
    lo, hi, alpha = t["words_min"], t["words_max"], t["words_alpha"]
    u = (np.arange(n) + 0.5) / n
    tail = 1.0 - (lo / hi) ** alpha
    words = lo / (1.0 - u * tail) ** (1.0 / alpha)
    return [int(w) for w in rng(seed, "words").permutation(words)]


def document(seed: int, idx: int, words: int) -> Dict[str, Any]:
    """Noise sentences with a tagged fact about every twelfth sentence."""
    r = rng(seed, f"doc{idx}")
    vocab = np.asarray(NOISE_WORDS)
    sents: List[str] = []
    total = 0
    while total < words:
        if r.random() < 1 / 12:
            tag = TAGS[int(r.integers(len(TAGS)))]
            value = "v" + r.bytes(4).hex()
            sents.append(f"the record notes a [{tag}] matter involving "
                         f"{value}.")
            total += 8
            continue
        n = int(r.integers(8, 18))
        sents.append(" ".join(vocab[r.integers(len(vocab), size=n)]) + ".")
        total += n
    return {"id": f"s{idx}", "text": " ".join(sents)}


def documents(t: Dict[str, Any], n: int, seed: int) -> List[Dict[str, Any]]:
    return [document(seed, i, w)
            for i, w in enumerate(word_counts(t, n, seed))]


def arrivals(t: Dict[str, Any], seconds: float, seed: int) -> List[float]:
    """Due times (seconds from the window's start) of open-loop traffic:
    ``round(rate * seconds)`` exponential-quantile gaps in an order drawn
    from the traffic's name (the same for every seed), the arrivals that
    fall inside the window."""
    del seed
    rate = float(t["rate_per_s"])
    n = max(1, round(rate * seconds))
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n) / rate
    due = np.cumsum(rng(0, f"gaps|{t['name']}").permutation(gaps))
    return [float(x) for x in due if x < seconds]


def pipeline(t: Dict[str, Any], model: str) -> Dict[str, Any]:
    """The served plan: one operator of the traffic's kind."""
    op = dict(t["operator"], model=model)
    return {"name": f"{t['name']}@{model}", "operators": [op]}


def _hash_id(word: str, vocab: int) -> int:
    h = int.from_bytes(hashlib.blake2s(word.encode()).digest()[:4], "little")
    return N_SPECIAL + h % (vocab - N_SPECIAL)


def prompt_text(op: Dict[str, Any], doc: Dict[str, Any]) -> str:
    return f"{op.get('prompt', '')}\n{doc['text'][:PROMPT_CHARS]}"


def prompt_ids(op: Dict[str, Any], doc: Dict[str, Any], vocab: int
               ) -> List[int]:
    words = _WORD_RE.findall(prompt_text(op, doc))
    return [BOS_ID] + [_hash_id(w, vocab)
                       for w in words[:MAX_PROMPT_TOKENS - 1]]


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile (no interpolation)."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no values")
    return vals[max(1, math.ceil(q / 100.0 * len(vals))) - 1]
