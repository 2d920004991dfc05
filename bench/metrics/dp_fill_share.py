"""Share of the DP columns the device row solver computed that its rows
needed: the growth of the program's own ``dp_cols_needed`` (each DP row's
``ceil(residual / g) + 1``) over that of ``dp_cols_computed`` (the static
width of the residual tier each such row ran at) in the window, in
percent (program counter).  None where the program keeps no such
counters, or no row reached the DP stages."""


def read(run):
    if not run.counters.get("dp_rows"):
        return None
    return (100.0 * run.counters["dp_cols_needed"]
            / run.counters["dp_cols_computed"])
