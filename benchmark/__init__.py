"""The yardstick: BENCHMARK.json's harness, traffic, reference and readers.

Later PRs add files here and edit none (see README.md)."""
