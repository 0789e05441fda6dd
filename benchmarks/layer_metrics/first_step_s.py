"""Job set-up and compile: entry of the job to the completion of the first
step (pool, build, init, lowering, compile or cache read, first dispatch)."""


def read(run: dict) -> float | None:
    if run["t_first_step"] is None:
        return None
    return run["t_first_step"] - run["t_job"]
