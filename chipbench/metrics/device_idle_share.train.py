"""Share of the traced window in which no operation ran on the device, %
(averaged over the chips)."""


def read(run):
    tr = run.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0
