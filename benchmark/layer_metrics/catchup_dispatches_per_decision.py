"""dispatches_per_decision's reader, where catchup_blocks_per_s is the metric."""

from benchmark.layer_metrics.dispatches_per_decision import read  # noqa: F401
