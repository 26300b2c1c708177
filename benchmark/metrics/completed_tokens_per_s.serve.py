"""Generated tokens of the requests completed inside the window over
the window's seconds.  Under the knee it follows the offered load (which
requests end before the close varies with the seed, so it is no
end-to-end metric here); a saturated cell would judge it (PERF.md)."""


def read(run):
    if run["kind"] != "decode":
        return None
    return run["completed_tokens_per_s"]
