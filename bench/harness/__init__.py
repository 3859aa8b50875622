"""Shared code of the chip benchmark: cell lookup, traffic generation,
weights from a seed, the driver, the trace reduction, work functions,
the peak table and the correctness check.  Everything that belongs to
one configuration, traffic mix or per-layer metric lives in a file of
its own under ``bench/configs``, ``bench/traffic`` or ``bench/metrics``
and is found by name."""
