"""A per-layer metric added from the tests' own directory: the steps the
trace after the window covered.  Nothing to read in an untraced run."""


def read(run: dict) -> float | None:
    steps = run.get("trace_steps")
    return None if steps is None else float(steps)
