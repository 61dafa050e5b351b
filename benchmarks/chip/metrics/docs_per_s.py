"""docs_per_s: documents completed per second. The server resolves a
batch's tickets together, so completions come in groups; the rate is the
documents of every group after the window's first, over the time from
the first group to the last (all on the benchmark's clock)."""


def read(run):
    w0, w1 = run.window
    groups = {}
    for r in run.reqs:
        if r.done_at is not None and r.ticket.error is None \
                and w0 <= r.done_at < w1:
            groups.setdefault(r.ticket.finished_at, []).append(r.done_at)
    ends = sorted((min(v), len(v)) for v in groups.values())
    if len(ends) < 2:
        return None
    return sum(n for _, n in ends[1:]) / (ends[-1][0] - ends[0][0])
