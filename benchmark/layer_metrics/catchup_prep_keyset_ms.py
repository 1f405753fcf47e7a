"""sync_prep_keyset_ms's reader, where catchup_blocks_per_s is the metric."""

from benchmark.layer_metrics.sync_prep_keyset_ms import read  # noqa: F401
