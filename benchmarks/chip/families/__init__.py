"""Model families: per family, the plain float32 reference, the weights
drawn from a seed the way the served program draws them, and the
operation and byte counts the roofline and utilization metrics divide
by. A configuration file names its family; ``layout.family`` loads it.
"""
