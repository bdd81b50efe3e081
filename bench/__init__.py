"""The chip benchmark of the graph engine: one cell per run, driven by
the entries of ``BENCHMARK.json`` and the files they name under here."""
