"""sync_device_idle_share's reader, where catchup_blocks_per_s is the metric."""

from benchmark.layer_metrics.sync_device_idle_share import read  # noqa: F401
