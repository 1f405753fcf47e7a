"""decisions_per_s's reader under the name hub-150.fastsync reports it by: one
name carries one bound, and that cell's runs spread more widely than the
bound fastsync-1k-mixed.replay is held to (PERF.md section 2)."""

from benchmark.end_to_end.decisions_per_s import read  # noqa: F401
