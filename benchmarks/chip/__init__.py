"""The chip benchmark: one cell of ``BENCHMARK.json`` per run of
``run.py``. See ``run.py`` for how a run goes and ``layout.py`` for where
each piece of a cell is found."""
