"""Arrays handed to the device per engine call (the program's own
`h2d_arrays` counter of `vectorsim._dispatch`): sum of `h2d_arrays` /
sum of `engine_calls` over the window's queries (set together on every
engine call)."""
from bench.readers import stat_mean


def read(run):
    arrays = stat_mean(run, "run", "h2d_arrays")
    calls = stat_mean(run, "run", "engine_calls")
    return None if arrays is None or not calls else arrays / calls
