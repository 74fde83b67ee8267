"""The chip benchmark's yardstick: traffic generation, latency arithmetic,
weights made from the seed, the plain float32 reference, the trace
reduction, the peak table and the work counts of each kernel.

Nothing here is imported by the program under test. Everything that belongs
to one configuration, traffic mix, cell or per-layer metric lives in a file
of its own beside this package and is found by its name (see `spec`).
"""
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(os.path.dirname(BENCH_DIR))
