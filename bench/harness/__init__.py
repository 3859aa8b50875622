"""Shared code of the chip benchmark: cell lookup, traffic generation,
the seeded draw of weights, the driver, the trace reduction, the packed
projection's work, the peak table and the correctness check.
Everything that belongs to one configuration, traffic mix, model family,
plain reference or per-layer metric lives in a file of its own under
``bench/configs``, ``bench/traffic``, ``bench/families``,
``bench/references`` or ``bench/metrics`` and is found by name."""
