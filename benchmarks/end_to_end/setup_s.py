"""Process start (the clock read before jax is imported) to the opening of
the measured window: imports, template -> provision -> contract -> launch
plan, build, compile or cache read, first step, warm-up."""


def read(run: dict) -> float:
    return run["times"][run["window"][0]] - run["t_process"]
