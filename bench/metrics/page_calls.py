"""Engine calls per query (the program's own `engine_calls` counter of
`vectorsim._dispatch`): one a page where every page runs once, more
where pages are run again."""
from bench.readers import stat_mean


def read(run):
    return stat_mean(run, "run", "engine_calls")
