"""The chip benchmark of the FLaaS scheduling service (see BENCHMARK.json)."""
