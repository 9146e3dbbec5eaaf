"""Benchmark harness: paper tables/figures and serving benches."""
