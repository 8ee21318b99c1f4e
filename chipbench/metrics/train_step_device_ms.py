"""Device busy time per train step in the traced window, ms: the busy time
over the launches of the program that took most of it."""


def read(run):
    tr = run.get("trace")
    if not tr or not tr["module_s"]:
        return None
    step = max(tr["module_s"], key=tr["module_s"].get)
    return tr["busy_s"] / tr["module_n"][step] * 1e3
