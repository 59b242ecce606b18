"""From a profiler trace to per-layer numbers.

``xplane`` turns the ``.xplane.pb`` file JAX's profiler writes into a small
plain form (device events and the window's host marker, on the trace's
clock); ``reduce`` computes busy time, idle share, kernel time, programs
per batch and the idle-gap breakdown from that form alone, so the
reduction can be checked on a committed fixture without a chip.
"""
