"""Job set-up and compile: completion of the first step to the opening of
the measured window: the steps the output check follows, the probe's two
programs (compile or cache read), and `warm_seconds` of steps.  With
`process_start_s`, `provision_s` and `first_step_s` it adds up to
`setup_s`."""


def read(run: dict) -> float | None:
    if run["t_first_step"] is None or run["window"] is None:
        return None
    return run["times"][run["window"][0]] - run["t_first_step"]
