"""entry.cache_hit_share (%; layer: entry points; moves setup_s).

Share of persistent-cache look-ups during set-up that hit.
"""

META = {"name": "entry.cache_hit_share", "unit": "%", "layer": "entry points",
        "moves": "setup_s"}


def read(run):
    s = run.get("setup")
    if not s or s["cache_hits"] + s["cache_misses"] == 0:
        return None
    return 100.0 * s["cache_hits"] / (s["cache_hits"] + s["cache_misses"])
