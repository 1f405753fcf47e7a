"""apply_ms's reader, where catchup_blocks_per_s is the metric."""

from benchmark.layer_metrics.apply_ms import read  # noqa: F401
