"""Compile cache: of the programs that reached the backend during set-up,
the share (%) the persistent cache served. 100 on a warm run means no
program was compiled that the cache could have held. Moves ``setup_s``."""


def read(run):
    programs = run.setup["programs"]
    if not programs:
        return None
    return 100.0 * run.setup["cache_hits"] / programs
