"""Program cache (``exec/programs.py``): programs the registry added in
the window plus the persistent cache's hits and misses there (the mesh
tier's programs are jitted outside the registry, so both are counted).
Must be 0: nothing compiles and nothing is loaded inside the window."""

NAME = "compiles_in_window"
UNIT = "count"


def read(run):
    return float(sum(run.counters.values()))
