"""Per-layer metric readers, one file per metric, found by the metric's name.

Each file defines ``read(ctx)`` → a number, or None when the run holds
nothing to read (the metric is then left out of the result line). ``ctx``
is a `bench.harness.Context`: the reduced trace, the configuration, the
mix, the device's peaks and the load generator's record.
"""
