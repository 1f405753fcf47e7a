"""verify_kernel_roofline's reader, where catchup_blocks_per_s is the metric:
this cell adds no kernel, and the one it drives reports its share of the
measured int32 peak from this cell's own trace."""

from benchmark.layer_metrics.verify_kernel_roofline import read  # noqa: F401
