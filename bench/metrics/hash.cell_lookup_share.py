"""The share of the hash encode's corner lookups that cell-major rows
serve: the program's `hash.cell_lookups` (8 a point on each
direct-indexed level whose cell-major table is staged, one gathered row
holding all 8) over its `hash.lookups` (8 a point on every level of
every field queried), counted per slot from the fields' static shapes."""


def read(run):
    counters = run["stats"].get("trace", {}).get("counters", {})
    lookups = counters.get("hash.lookups", 0)
    if lookups == 0:
        return None
    return 100.0 * counters.get("hash.cell_lookups", 0) / lookups
