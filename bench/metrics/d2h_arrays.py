"""Arrays copied back per engine call (the program's own `d2h_arrays`
counter of `vectorsim._dispatch`): sum of `d2h_arrays` / sum of
`engine_calls` over the window's queries (set together on every engine
call)."""
from bench.readers import stat_mean


def read(run):
    arrays = stat_mean(run, "run", "d2h_arrays")
    calls = stat_mean(run, "run", "engine_calls")
    return None if arrays is None or not calls else arrays / calls
