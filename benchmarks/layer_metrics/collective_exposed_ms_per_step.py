"""Parallel: time of device 0's collectives that no other operation covers
(`trace_reduce.py` `collective_exposed_s`), per executed program of the
traced window, in milliseconds."""


def read(run: dict) -> float | None:
    trace = run.get("trace")
    if not trace or not trace["per_device"] or not trace["per_device"][0]["programs"]:
        return None
    first = trace["per_device"][0]
    return 1e3 * first["collective_exposed_s"] / first["programs"]
