"""Tier-1's window onto the benchmark's own tests (``benchmarks/tests``,
which ``pytest tests/`` does not reach): one module here a test file
there, each importing that file's tests and fixtures, so that every one of
them is collected once. None needs a chip."""
