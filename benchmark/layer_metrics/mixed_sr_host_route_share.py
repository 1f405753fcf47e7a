"""sr25519 signatures the host verifier answered (prep.host_verify tagged
kind=sr25519) over all sr25519 signatures that went through the registry in
the window (those and the prep.launch spans of an sr25519 program), %."""

from benchmark.harness import spans


def read(run):
    if not run.traced or not spans._program_has("state.save"):
        return None
    host = sum(s["tags"].get("sigs", 0) for s in run.spans
               if s["name"] == "prep.host_verify"
               and s["tags"].get("kind") == "sr25519")
    device = sum(s["tags"].get("sigs", 0) for s in run.spans
                 if s["name"] == "prep.launch"
                 and "_sr_" in s["tags"].get("program", ""))
    return 100.0 * host / (host + device) if host + device else None
