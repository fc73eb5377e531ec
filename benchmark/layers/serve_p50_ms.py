"""Median latency from due time to result over the counted requests. It
stands here and not among the end-to-end metrics because it does not
repeat well enough for a bound: replies complete a batch at a time, so 720
requests are about a hundred independent readings of a spread-out
distribution (PERF.md, section 2)."""


def read(run):
    return run["readings"].get("serve_p50_ms")
