"""setup_s: seconds from the process's start to the first timed request:
imports, the backend's weights drawn on the chip from the seed, its
compile lint, compile-cache loads and the warm-up of the cell's shapes."""


def read(run):
    return run.setup_s
