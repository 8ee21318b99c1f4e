"""Device time of one paged prefill, ms (trace, by program name)."""
PROGRAM = "jit__prefill_paged"


def read(run):
    tr = run.get("trace")
    if not tr or not tr["module_n"].get(PROGRAM):
        return None
    return tr["module_s"][PROGRAM] / tr["module_n"][PROGRAM] * 1e3
