"""The provisioning planner's benchmark: one cell per run, driven by the
entries of ``BENCHMARK.json`` and the files they name."""
