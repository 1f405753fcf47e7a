"""Sign bytes that the per-commit splice produced over all the window's sign
bytes, %: the spliced and sigs tags of every commit.assemble (the rest took
the per-index fallback). Nothing where no span carries the tag, as from a
program before Commit.sign_bytes_many."""


def read(run):
    if not run.traced:
        return None
    tagged = [s["tags"] for s in run.spans
              if s["name"] == "commit.assemble" and "spliced" in s["tags"]]
    sigs = sum(t["sigs"] for t in tagged)
    if not sigs:
        return None
    return 100.0 * sum(t["spliced"] for t in tagged) / sigs
