"""host_prep_cpu_ms's reader, where decisions_per_s is the metric."""

from benchmark.layer_metrics.host_prep_cpu_ms import read  # noqa: F401
