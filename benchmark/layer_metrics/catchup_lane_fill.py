"""sync_lane_fill's reader, where catchup_blocks_per_s is the metric."""

from benchmark.layer_metrics.sync_lane_fill import read  # noqa: F401
