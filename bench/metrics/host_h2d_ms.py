"""Host time per query handing the engine's arguments to the device
(`jnp.asarray` of each; the program's own `h2d_s` span inside
`vectorsim._dispatch`), in ms."""
from bench.readers import ms, stat_mean


def read(run):
    return ms(stat_mean(run, "run", "h2d_s"))
