"""decode_step_ms.batch: mean device time of one execution of the jitted
decode step (``jit_serve_step``), in the traced window."""


def read(run):
    steps = run.trace and run.trace["decode_step_s"]
    return 1e3 * sum(steps) / len(steps) if steps else None
