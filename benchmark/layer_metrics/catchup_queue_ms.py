"""queue_ms's reader, where catchup_blocks_per_s is the metric."""

from benchmark.layer_metrics.queue_ms import read  # noqa: F401
