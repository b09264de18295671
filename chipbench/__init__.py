"""Chip benchmark for the EconoServe serving stack.

Everything the yardstick needs lives here: traffic generation from data
files, the model configurations as run, the plain float32 reference, the
reduction from a device trace to metrics, the table of peaks and the work
counts. The program under test is imported only by ``chipbench.run``.
"""
