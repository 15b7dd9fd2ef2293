"""How late the load generator sent the window's requests (send − due), p95.
A starved generator must not be read as a fast server."""
SOURCE = "client"


def compute(src):
    from loadgen import percentile

    late = src.client.get("late_s")
    return 1000.0 * percentile(late, 95) if late else None
