"""The on-chip benchmark of the iELAS stereo service (see ``BENCHMARK.json``)."""
