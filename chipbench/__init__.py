"""chipbench: the chip benchmark of this repository (see BENCHMARK.json)."""
