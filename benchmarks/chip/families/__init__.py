"""Model families: per family, the plain float32 reference, the weights
drawn from a seed the way the served program draws them, and the
operation and byte counts the roofline and utilization metrics divide
by. A configuration file names its family; ``layout.family`` loads it.

A family module gives ``PROGRAM_KEYS``, ``init_weights``, ``logits``,
``decode_cost``, ``prefill_flops``, ``param_bytes`` and, for the tests,
``from_program``: the served program's parameter tree under the
reference's names.
"""
