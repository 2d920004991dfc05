"""Share of the device row solver's DP rows that took the gcd rung of the
demand-coarsening ladder (granularity g > 1, DESIGN.md §14): the growth
of the program's own ``gcd_rows`` over that of ``dp_rows`` in the window,
in percent (program counter).  None where the program keeps no such
counters, or no row reached the DP stages."""


def read(run):
    if not run.counters.get("dp_rows"):
        return None
    return 100.0 * run.counters["gcd_rows"] / run.counters["dp_rows"]
