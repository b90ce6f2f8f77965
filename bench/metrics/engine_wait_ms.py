"""Host time per query waiting for the engine's outputs to be ready
(`jax.block_until_ready`; the program's own `wait_s` span inside
`vectorsim._dispatch`), in ms."""
from bench.readers import ms, stat_mean


def read(run):
    return ms(stat_mean(run, "run", "wait_s"))
