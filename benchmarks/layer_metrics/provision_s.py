"""Operator CLI to launch plan: entry of `cli.main` to entry of the job's
`main`, on the host clock."""


def read(run: dict) -> float:
    return run["t_job"] - run["t_cli"]
