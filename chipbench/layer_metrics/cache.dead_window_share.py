"""Share of the KV pool's bytes held by pages of window cache groups that lie
wholly behind their sequence's window (``dead_window_pages`` of the flight
records, mean over the window): what releasing them would free."""
SOURCE = "flight"


def compute(src):
    from sources import mean

    facts = src.facts or {}
    page = [g["page_bytes"] for g in facts.get("cache_groups", [])
            if g.get("window")]
    if not page or not facts.get("kv_bytes") or not src.flight:
        return None
    dead = mean([s.get("dead_window_pages", 0) for s in src.flight])
    return 100.0 * dead * (sum(page) / len(page)) / facts["kv_bytes"]
