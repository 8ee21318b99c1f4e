"""Share of set-up's compile requests that the persistent cache answered,
%: 0 in a cold checkout, 100 once every program is cached."""


def read(run):
    s = run["compiles"]["setup"]
    if not s["backend_compiles"]:
        return None
    return s["persistent_cache_hits"] / s["backend_compiles"] * 100.0
