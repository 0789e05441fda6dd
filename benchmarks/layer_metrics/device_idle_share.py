"""Device: 1 - union of device-operation intervals / traced window on
device 0, in percent."""


def read(run: dict) -> float | None:
    trace = run.get("trace")
    if not trace or not trace["per_device"] or not trace["per_device"][0]["window_s"]:
        return None
    first = trace["per_device"][0]
    return 100.0 * (1.0 - first["busy_s"] / first["window_s"])
