"""requests_per_launch's reader, where catchup_blocks_per_s is the metric."""

from benchmark.layer_metrics.requests_per_launch import read  # noqa: F401
