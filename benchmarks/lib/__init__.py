"""Shared code of the benchmark: the yardstick later PRs may not edit."""
