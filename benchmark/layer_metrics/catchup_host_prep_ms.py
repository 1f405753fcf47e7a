"""sync_host_prep_ms's reader, where catchup_blocks_per_s is the metric."""

from benchmark.layer_metrics.sync_host_prep_ms import read  # noqa: F401
