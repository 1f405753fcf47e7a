"""95th percentile of the traced run's decision times, ms: the tail with the
flight recorder on. Left out where the window holds under 200 decisions
(stats.percentile refuses a tail with fewer than ten samples beyond it). An
end-to-end metric it is not: between runs of the same code it spread by up
to 7% (PERF.md, PR 22)."""

from benchmark.harness import stats


def read(run):
    samples = run.latencies_ms()
    if len(samples) < stats.min_samples(95):
        return None
    return stats.percentile(samples, 95)
