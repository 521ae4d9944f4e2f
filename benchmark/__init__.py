"""The repository's benchmark: ``python benchmark/run.py`` (see PERF.md)."""
