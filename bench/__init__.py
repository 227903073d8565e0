"""On-chip benchmark of Dif-MAML meta-training (see ``BENCHMARK.json``)."""
