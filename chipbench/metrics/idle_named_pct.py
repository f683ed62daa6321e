"""Of the device's idle seconds that the trace reduction lists (the ten
largest entries of ``breakdown.idle_gaps``), the share under a span of the
program (``matcha/...``): the rest is "unattributed" or the harness's."""


def read(run):
    if not run["trace"]:
        return None
    gaps = run["trace"]["breakdown"]["idle_gaps"]
    listed = sum(seconds for _, seconds in gaps)
    named = sum(seconds for name, seconds in gaps
                if name.startswith("matcha/"))
    return 100.0 * named / listed if listed else None
