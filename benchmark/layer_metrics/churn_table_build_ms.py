"""startup.table_build records that began inside the window, ms per key
built: what one validator the device table has never held costs when it
first stands in a light prefix, a whole tile's build on the verify
service's thread. (A pass's first launch builds the genesis prefix's keys
in one tile, so the mean is over both kinds of build.)"""

from benchmark.layer_metrics.churn_table_fill import window_builds


def read(run):
    builds = window_builds(run)
    if not builds:
        return None
    return (sum(s.duration_s for s in builds) * 1e3
            / sum(s.tags["keys"] for s in builds))
