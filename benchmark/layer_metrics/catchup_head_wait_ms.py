"""head_wait_ms's reader, where catchup_blocks_per_s is the metric."""

from benchmark.layer_metrics.head_wait_ms import read  # noqa: F401
