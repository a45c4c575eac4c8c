"""Benchmark harness of the decentralized training system (see harness.py)."""
