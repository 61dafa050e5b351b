"""decode_roofline.batch: the least time one decode step could take on
this chip, over the mean device time the step took in the traced window.

The least time of a step is the larger of its operations over the peak
rate and its bytes over the memory bandwidth, with the counts of
``families/<family>.py``: what the algorithm needs for the slots in use
(the most common submit size, which the batcher fills), not what the
program moves. It is averaged over the positions a request decodes at."""

from collections import Counter

from benchmarks.chip import traffic as T


def least_step_s(run):
    n = Counter(c[2] for c in run.chunks).most_common(1)[0][0]
    new = run.traffic["server"]["max_new_tokens"]
    p = run.peaks
    times, bound = [], Counter()
    for pos in range(T.MAX_PROMPT_TOKENS, T.MAX_PROMPT_TOKENS + new - 1):
        flops, nbytes = run.family.decode_cost(run.sizes, n, pos)
        tf, tb = flops / p["bf16_flops_per_s"], nbytes / p["hbm_bytes_per_s"]
        times.append(max(tf, tb))
        bound["memory" if tb >= tf else "compute"] += 1
    return sum(times) / len(times), bound.most_common(1)[0][0], n


def read(run):
    steps = run.trace and run.trace["decode_step_s"]
    if not steps or not run.chunks:
        return None
    least, bound, n = least_step_s(run)
    measured = sum(steps) / len(steps)
    print(f"decode_roofline.batch: {bound}-bound at {n} slots in use, "
          f"least {least} s, measured {measured} s per step")
    return 100.0 * least / measured
