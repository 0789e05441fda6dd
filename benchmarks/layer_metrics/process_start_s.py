"""Process start and runtime: the clock read before `jax` is imported to
the entry of `cli.main`: Python's imports of the benchmark, of `jax` and of
the program, and the TPU runtime's start when the devices are first asked
for."""


def read(run: dict) -> float:
    return run["t_cli"] - run["t_process"]
