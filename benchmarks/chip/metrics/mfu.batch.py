"""mfu.batch: the model operations of the decode steps that the device ran
in the traced window, over that window at the chip's peak bf16 rate.

A step's operations are those of the slots in use (the most common
submit size, which the batcher fills), averaged over the positions a
request decodes at, from the counts of ``families/<family>.py``. Idle
time counts against it, so it moves with ``docs_per_s``. Prefills are
left out (about a quarter of a document's operations), so it reads low,
never high."""

from collections import Counter

from benchmarks.chip import traffic as T


def step_flops(run) -> float:
    n = Counter(c[2] for c in run.chunks).most_common(1)[0][0]
    new = run.traffic["server"]["max_new_tokens"]
    positions = range(T.MAX_PROMPT_TOKENS, T.MAX_PROMPT_TOKENS + new - 1)
    return sum(run.family.decode_cost(run.sizes, n, pos)[0]
               for pos in positions) / len(positions)


def read(run):
    tr = run.trace
    if not tr or not tr["decode_step_s"] or not run.chunks \
            or tr["window_s"] <= 0:
        return None
    # every chip runs each step: count the steps once, the peak per chip
    steps = len(tr["decode_step_s"]) / tr["devices"]
    peak = tr["devices"] * run.peaks["bf16_flops_per_s"]
    return 100.0 * steps * step_flops(run) / (tr["window_s"] * peak)
