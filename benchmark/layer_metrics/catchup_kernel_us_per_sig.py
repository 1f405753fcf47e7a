"""sync_kernel_us_per_sig's reader, where catchup_blocks_per_s is the metric."""

from benchmark.layer_metrics.sync_kernel_us_per_sig import read  # noqa: F401
