"""light.skip.trusting spans (a whole verify_commit_light_trusting: the scan
by address in the trusted set, dispatch, wait, tally) of the accepted hops,
ms per accepted hop. A refused attempt's check is not in it: the overlap it
found was under the trust level."""

from benchmark.harness import skip


def read(run):
    return skip.ms_per(run, "light.skip.trusting", 1)
