"""Keys built over tile rows built, %, over the startup.table_build records
that began inside the window (tags keys, rows): the useful share of the
device's table builds, 1/256 = 0.39% for a build of one joiner."""


def window_builds(run) -> list | None:
    """The start-up ring's table builds that began inside the window and
    say how many rows they built; None where the program's do not say."""
    from tendermint_tpu.utils import trace

    ring = getattr(trace, "STARTUP", None)
    if ring is None or run.window is None or run.window[1] is None:
        return None
    t0, t1 = run.window
    return [s for s in ring.dump() if s.name == "startup.table_build"
            and t0 <= s.start < t1 and "rows" in s.tags]


def read(run):
    builds = window_builds(run)
    if not builds:
        return None
    return (100.0 * sum(s.tags["keys"] for s in builds)
            / sum(s.tags["rows"] for s in builds))
